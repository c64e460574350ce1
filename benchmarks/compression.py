"""Cross-pod gradient compression — HLO wire-byte evidence.

Lowers the per-pod gradient synchronization both ways on the production
2x16x16 mesh and counts collective bytes in the compiled modules: the int8
error-feedback compressor (repro.runtime.compression) must cut the
pod-axis (DCN) payload ~4x vs f32 / ~2x vs bf16.

Runs in a subprocess so the 512-device XLA flag never leaks into the
benchmark process (the dry-run rule).  The child is pinned to the CPU
(``JAX_PLATFORMS=cpu``): on a TPU host the parent already holds the chip.
"""
from __future__ import annotations

import json
import subprocess
import sys

_CHILD = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
import json
import jax, jax.numpy as jnp
from functools import partial
shard_map = partial(jax.shard_map, check_vma=False)
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.launch import hlo
from repro.launch.mesh import make_production_mesh
from repro.runtime.compression import compressed_psum

mesh = make_production_mesh(multi_pod=True)
g_sds = jax.ShapeDtypeStruct((4096, 5120), jnp.float32)   # a grad shard
e_sds = jax.ShapeDtypeStruct((4096, 5120), jnp.float32)
sh = NamedSharding(mesh, P(None, "model"))

def plain(g):
    f = shard_map(lambda x: jax.lax.psum(x, "pod"), mesh=mesh,
                  in_specs=P(None, "model"), out_specs=P(None, "model"))
    return f(g)

def compressed(g, err):
    f = shard_map(lambda x, e: compressed_psum(x, "pod", e), mesh=mesh,
                  in_specs=(P(None, "model"), P(None, "model")),
                  out_specs=(P(None, "model"), P(None, "model")))
    return f(g, err)

out = {}
txt = jax.jit(plain, in_shardings=(sh,)).lower(g_sds).compile().as_text()
out["plain"] = hlo.collective_bytes(txt)
txt = jax.jit(compressed, in_shardings=(sh, sh)).lower(g_sds, e_sds)\
    .compile().as_text()
out["compressed"] = hlo.collective_bytes(txt)
print(json.dumps(out))
"""


def run():
    proc = subprocess.run([sys.executable, "-c", _CHILD],
                          capture_output=True, text=True, timeout=600,
                          env={**__import__("os").environ,
                               "PYTHONPATH": "src", "JAX_PLATFORMS": "cpu"})
    if proc.returncode != 0:
        raise RuntimeError(proc.stderr[-1500:])
    data = json.loads(proc.stdout.strip().splitlines()[-1])
    plain_b = data["plain"]["total"]
    comp_b = data["compressed"]["total"]
    return [
        ("compression/plain_psum_pod_mb", plain_b / 1e6, "f32_grad_shard"),
        ("compression/int8_ef_psum_pod_mb", comp_b / 1e6,
         f"wire_reduction={plain_b / max(comp_b, 1):.2f}x"),
    ]


if __name__ == "__main__":
    for name, val, derived in run():
        print(f"{name},{val:.3f},{derived}")
