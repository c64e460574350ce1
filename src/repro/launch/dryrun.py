import os
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# The lines above MUST run before any other import (jax locks the platform
# and device count at first init; on a TPU host the dry-run must not take
# the chip). 512 placeholder host devices let jax.make_mesh build
# the production (2, 16, 16) multi-pod mesh for lower()+compile() without
# hardware. Dry-run only — smoke tests and benchmarks see the real 1 device.

import argparse      # noqa: E402
import json          # noqa: E402
import time          # noqa: E402
import traceback     # noqa: E402

import jax           # noqa: E402
import jax.numpy as jnp  # noqa: E402

import repro.configs as configs                       # noqa: E402
from repro.dist import params as dist_params          # noqa: E402
from repro.dist import sharding as dist_sharding      # noqa: E402
from repro.launch import hlo, steps                   # noqa: E402
from repro.launch.mesh import make_production_mesh    # noqa: E402
from repro.launch.shapes import (SHAPES, applicable, cache_capacity,  # noqa: E402
                                 decode_src_len, input_specs)
from repro.models import transformer as tf            # noqa: E402

# TPU v5e roofline constants (per chip)
PEAK_FLOPS = 197e12       # bf16
HBM_BW = 819e9            # bytes/s
ICI_BW = 50e9             # bytes/s/link


def _microbatches(cfg) -> int:
    if cfg.d_model >= 4096:
        return 8
    if cfg.d_model >= 3072:
        return 4
    return 2


def model_flops(cfg, cell) -> float:
    """Global MODEL_FLOPS = c * N(_active) * tokens (c: 6 train, 2 fwd)."""
    n = cfg.active_param_count()
    if cell.kind == "train":
        return 6.0 * n * cell.batch * cell.seq
    if cell.kind == "prefill":
        return 2.0 * n * cell.batch * cell.seq
    return 2.0 * n * cell.batch          # decode: one token per row


def lower_cell(arch: str, shape_name: str, multi_pod: bool,
               triangle_skip: bool = False, attribute: bool = False):
    """Build + lower + compile one cell; returns the result record."""
    cfg = configs.get(arch)
    cell = SHAPES[shape_name]
    rec = {
        "arch": arch, "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "kind": "attribute" if attribute else cell.kind,
        "triangle_skip": triangle_skip,
    }
    ok, reason = applicable(cfg, shape_name)
    if not ok:
        rec.update(status="skipped", reason=reason)
        return rec

    mesh = make_production_mesh(multi_pod=multi_pod)
    n_chips = mesh.devices.size
    t0 = time.time()
    key = jax.random.PRNGKey(0)

    with dist_sharding.use_mesh(mesh):
        if attribute:
            params_sds = jax.eval_shape(lambda k: tf.init(k, cfg), key)
            p_sh = dist_params.param_sharding_tree(params_sds, mesh)
            batch_sds = input_specs(cfg, shape_name)
            b_sh = steps.batch_shardings(batch_sds, mesh)
            step = steps.make_attribute_step(cfg, "saliency",
                                             triangle_skip=triangle_skip)
            jitted = jax.jit(step, in_shardings=(p_sh, b_sh))
            lowered = jitted.lower(params_sds, batch_sds)
        elif cell.kind == "train":
            init_fn = steps.make_train_state_init(cfg)
            state_sds = jax.eval_shape(init_fn, key)
            s_sh = steps.state_shardings(state_sds, mesh)
            batch_sds = input_specs(cfg, shape_name)
            b_sh = steps.batch_shardings(batch_sds, mesh)
            micro = _microbatches(cfg)
            rec["microbatches"] = micro
            step = steps.make_train_step(cfg, microbatches=micro,
                                         triangle_skip=triangle_skip)
            jitted = jax.jit(step, in_shardings=(s_sh, b_sh),
                             out_shardings=(s_sh, None), donate_argnums=(0,))
            lowered = jitted.lower(state_sds, batch_sds)
        elif cell.kind == "prefill":
            params_sds = jax.eval_shape(lambda k: tf.init(k, cfg), key)
            p_sh = dist_params.param_sharding_tree(params_sds, mesh)
            batch_sds = input_specs(cfg, shape_name)
            b_sh = steps.batch_shardings(batch_sds, mesh)
            cap = cache_capacity(shape_name)
            cache_sds = jax.eval_shape(
                lambda: tf.init_cache(cfg, cell.batch, cap,
                                      src_len=decode_src_len(cfg)))
            c_sh = steps.cache_shardings(cfg, cache_sds, mesh, cell.batch)
            step = steps.make_prefill_step(cfg, triangle_skip=triangle_skip)
            jitted = jax.jit(step, in_shardings=(p_sh, b_sh, c_sh),
                             out_shardings=(None, c_sh), donate_argnums=(2,))
            lowered = jitted.lower(params_sds, batch_sds, cache_sds)
        else:  # decode
            params_sds = jax.eval_shape(lambda k: tf.init(k, cfg), key)
            p_sh = dist_params.param_sharding_tree(params_sds, mesh)
            cap = cache_capacity(shape_name)
            cache_sds = jax.eval_shape(
                lambda: tf.init_cache(cfg, cell.batch, cap,
                                      src_len=decode_src_len(cfg)))
            c_sh = steps.cache_shardings(cfg, cache_sds, mesh, cell.batch)
            tok_sds = jax.ShapeDtypeStruct((cell.batch, 1), jnp.int32)
            tok_sh = steps.batch_shardings({"tokens": tok_sds}, mesh)["tokens"]
            if cell.batch < 32:      # replicated tiny batch (long_500k)
                tok_sh = jax.NamedSharding(mesh, jax.sharding.PartitionSpec())
            pos_sds = jax.ShapeDtypeStruct((), jnp.int32)
            pos_sh = jax.NamedSharding(mesh, jax.sharding.PartitionSpec())
            step = steps.make_decode_step(cfg)
            jitted = jax.jit(step, in_shardings=(p_sh, c_sh, tok_sh, pos_sh),
                             out_shardings=(None, c_sh), donate_argnums=(1,))
            lowered = jitted.lower(params_sds, cache_sds, tok_sds, pos_sds)

        compiled = lowered.compile()

    rec["lower_compile_s"] = round(time.time() - t0, 1)
    mem = hlo.memory_summary(compiled)
    cost = hlo.cost_summary(compiled)          # raw XLA (while-body-once)
    analysis = hlo.analyze(compiled.as_text())  # trip-count-aware
    coll = {k[5:]: int(v) for k, v in analysis.items() if k.startswith("coll_")}
    coll["total"] = int(analysis.get("collective_bytes", 0))
    rec.update(status="ok", memory=mem, cost_xla=cost, collectives=coll,
               analysis={k: v for k, v in analysis.items()
                         if not k.startswith("coll_")})

    # ---- roofline terms (per chip; analysis is per-device) ----
    # memory term uses the TPU-proxy bytes_major (see hlo.py); the all-
    # boundaries upper bound is recorded alongside as memory_s_upper.
    flops_dev = analysis.get("flops", 0.0)
    bytes_dev = analysis.get("bytes_major", analysis.get("bytes", 0.0))
    bytes_upper = analysis.get("bytes", 0.0)
    coll_dev = coll.get("total", 0)
    mf = model_flops(cfg, SHAPES[shape_name]) if not attribute else \
        4.0 * cfg.active_param_count() * SHAPES[shape_name].batch * SHAPES[shape_name].seq
    terms = {
        "compute_s": flops_dev / PEAK_FLOPS,
        "memory_s": bytes_dev / HBM_BW,
        "memory_s_upper": bytes_upper / HBM_BW,
        "collective_s": coll_dev / ICI_BW,
        "model_flops_global": mf,
        "hlo_flops_global": flops_dev * n_chips,
        "useful_flops_ratio": mf / max(flops_dev * n_chips, 1.0),
    }
    terms["bottleneck"] = max(
        ("compute_s", "memory_s", "collective_s"), key=lambda k: terms[k])
    rec["roofline"] = terms
    return rec


def main():
    ap = argparse.ArgumentParser(description="multi-pod dry-run")
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--mesh", default="both", choices=["single", "multi", "both"])
    ap.add_argument("--triangle-skip", action="store_true",
                    help="enable static causal-block skipping (optimized run)")
    ap.add_argument("--attribute", action="store_true",
                    help="lower attribute_step instead of the cell's kind")
    ap.add_argument("--out", default="benchmarks/results/dryrun.jsonl")
    args = ap.parse_args()

    archs = configs.ARCHS if args.arch == "all" else [args.arch]
    shape_names = list(SHAPES) if args.shape == "all" else [args.shape]
    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]

    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    n_fail = 0
    with open(args.out, "a") as f:
        for arch in archs:
            for shape_name in shape_names:
                for multi in meshes:
                    tag = f"{arch} x {shape_name} x {'multi' if multi else 'single'}"
                    try:
                        rec = lower_cell(arch, shape_name, multi,
                                         triangle_skip=args.triangle_skip,
                                         attribute=args.attribute)
                    except Exception as e:
                        rec = {"arch": arch, "shape": shape_name,
                               "mesh": "2x16x16" if multi else "16x16",
                               "status": "error",
                               "error": f"{type(e).__name__}: {e}",
                               "trace": traceback.format_exc()[-2000:]}
                        n_fail += 1
                    f.write(json.dumps(rec) + "\n")
                    f.flush()
                    status = rec.get("status")
                    extra = ""
                    if status == "ok":
                        r = rec["roofline"]
                        extra = (f" compile={rec['lower_compile_s']}s "
                                 f"bottleneck={r['bottleneck']} "
                                 f"compute={r['compute_s']*1e3:.1f}ms "
                                 f"mem={r['memory_s']*1e3:.1f}ms "
                                 f"coll={r['collective_s']*1e3:.1f}ms")
                    print(f"[{status:>7s}] {tag}{extra}", flush=True)
    if n_fail:
        raise SystemExit(f"{n_fail} cells failed")


if __name__ == "__main__":
    main()
