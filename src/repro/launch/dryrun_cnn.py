import os
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# Dry-run only — see dryrun.py for the device-count rule.

"""The paper's OWN model at pod scale: batched attribution serving of the
Table III CNN on the production mesh — the bridge between the paper's
batch-1 edge FPGA and a fleet endpoint ("explain every frame of a camera
stream").  Lowers attribute-batch programs for all three methods and
records the same artifact set as the LM dry-run.

    PYTHONPATH=src python -m repro.launch.dryrun_cnn [--batch 8192]
"""

import argparse   # noqa: E402
import json       # noqa: E402
import time       # noqa: E402

import jax                # noqa: E402
import jax.numpy as jnp   # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P   # noqa: E402

from repro.core import attribution          # noqa: E402
from repro.launch import hlo                # noqa: E402
from repro.launch.mesh import make_production_mesh   # noqa: E402
from repro.models import cnn                # noqa: E402

PEAK_FLOPS = 197e12
HBM_BW = 819e9
ICI_BW = 50e9


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, default=8192)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--out", default="benchmarks/results/dryrun_cnn.jsonl")
    args = ap.parse_args()

    cfg = cnn.CNNConfig()
    mesh = make_production_mesh(multi_pod=args.multi_pod)
    # batch-parallel over EVERY axis: the CNN is tiny, so the whole model
    # replicates and the batch shards 256/512 ways (the paper's edge unit,
    # fleet-parallel)
    all_axes = tuple(mesh.axis_names)
    x_sh = NamedSharding(mesh, P(all_axes, None, None, None))
    p_sh = jax.tree.map(lambda _: NamedSharding(mesh, P()),
                        jax.eval_shape(lambda k: cnn.init(k, cfg),
                                       jax.random.PRNGKey(0)))
    params_sds = jax.eval_shape(lambda k: cnn.init(k, cfg),
                                jax.random.PRNGKey(0))
    x_sds = jax.ShapeDtypeStruct((args.batch, 32, 32, 3), jnp.float32)

    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "a") as f:
        for method in ("saliency", "deconvnet", "guided"):
            t0 = time.time()

            def step(params, x, method=method):
                logits, rel = attribution.attribute(
                    lambda v: cnn.apply(params, v, cfg, method=method), x)
                return jnp.argmax(logits, -1), attribution.heatmap(rel)

            compiled = jax.jit(step, in_shardings=(p_sh, x_sh)).lower(
                params_sds, x_sds).compile()
            a = hlo.analyze(compiled.as_text())
            mem = hlo.memory_summary(compiled)
            rec = {
                "arch": "paper_cnn", "shape": f"attribute_b{args.batch}",
                "mesh": "2x16x16" if args.multi_pod else "16x16",
                "kind": "attribute", "method": method, "status": "ok",
                "lower_compile_s": round(time.time() - t0, 1),
                "memory": mem,
                "analysis": {k: v for k, v in a.items()
                             if not k.startswith("coll_")},
                "roofline": {
                    "compute_s": a.get("flops", 0) / PEAK_FLOPS,
                    "memory_s": a.get("bytes_major", 0) / HBM_BW,
                    "collective_s": a.get("collective_bytes", 0) / ICI_BW,
                },
            }
            f.write(json.dumps(rec) + "\n")
            r = rec["roofline"]
            print(f"[ok] paper_cnn attribute b{args.batch} {method}: "
                  f"compute={r['compute_s']*1e6:.1f}us "
                  f"mem={r['memory_s']*1e6:.1f}us "
                  f"coll={r['collective_s']*1e6:.1f}us "
                  f"temp={mem.get('temp_size_in_bytes', 0)/1e6:.1f}MB/chip",
                  flush=True)


if __name__ == "__main__":
    main()
