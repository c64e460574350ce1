"""2x2 max-pool + 2-bit argmax-index Pallas kernels (paper §III.D, Fig. 5).

The FPGA absorbs pooling into the output-store of the preceding layer and
caches a 2-bit index per window on-chip.  The TPU kernel reads the feature
map once from VMEM, emits the pooled map and the crumb-packed indices in the
same pass; the unpool BP routes gradients through strided VMEM stores that
cover every full-resolution position exactly once.

Window candidates come from a free HBM reshape, not strided loads: the
wrapper views [H, W, C] as [H/2, 2, W/2, 2C], so the window row is a
leading index and the window column is a lane half — max/argmax are 4-way
VPU selects on plain slices.

:func:`unpack_crumbs` and :func:`unpool_store` are IN-KERNEL helpers also
invoked by the fused conv backward kernel (conv2d/), where the unpool
routing runs as a prologue on the incoming gradient inside the conv-BP
pallas_call.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import carrier, interpret_mode, widen
from repro.kernels.relu_mask.relu_mask import pack_fields
from repro.kernels.tiling import CRUMBS_PER_BYTE, align_up, crumb_bytes

#: window positions (di, dj) in 2-bit index order
WINDOW = ((0, 0), (0, 1), (1, 0), (1, 1))


# ---------------------------------------------------------------------------
# in-kernel helpers (shared by the fused conv BP kernel)
# ---------------------------------------------------------------------------


def unpack_crumbs(packed: jnp.ndarray) -> jnp.ndarray:
    """[..., C/4] uint8 -> [..., C] int32 in 0..3 — VPU shift/and unpack."""
    shifts = 2 * jnp.arange(4, dtype=jnp.int32)
    idx = (packed.astype(jnp.int32)[..., None] >> shifts) & 3
    return idx.reshape(packed.shape[:-1] + (packed.shape[-1] * 4,))


def unpool_store(dst_ref, idx: jnp.ndarray, g: jnp.ndarray) -> None:
    """Route pooled grads [..., H/2, W/2, C] into ``dst_ref`` [..., H, W, C]
    (Fig. 5b): one stride-2 store per window position, covering every
    full-resolution element exactly once (no zero fill needed).

    ``idx`` ([H/2, W/2, C], values 0..3) broadcasts against ``g``'s leading
    axes — seed-batched gradients share one stored index map.  ``dst_ref``
    must hold a 32-bit dtype: Mosaic strides only 32-bit VMEM data.
    """
    hp, wp = g.shape[-3], g.shape[-2]
    lead = (slice(None),) * (g.ndim - 3)
    for k, (di, dj) in enumerate(WINDOW):
        at = lead + (pl.ds(di, hp, stride=2), pl.ds(dj, wp, stride=2),
                     slice(None))
        dst_ref[at] = jnp.where(idx == k, g, 0).astype(dst_ref.dtype)


# ---------------------------------------------------------------------------
# standalone kernels
# ---------------------------------------------------------------------------


def _pool_fwd_kernel(x_ref, y_ref, i_ref):
    x = widen(x_ref[0])                  # [H/2, 2, W/2, 2C]
    c = x.shape[-1] // 2
    best = idx = None
    for k, (di, dj) in enumerate(WINDOW):
        cand = x[:, di, :, dj * c:(dj + 1) * c]               # [H/2, W/2, C]
        if best is None:
            best, idx = cand, jnp.zeros(cand.shape, jnp.int32)
            continue
        take = cand > best               # strict: ties keep the first (argmax)
        best = jnp.where(take, cand, best)
        idx = jnp.where(take, k, idx)
    y_ref[0] = best.astype(y_ref.dtype)
    hp, wp, _ = idx.shape
    crumbs = pack_fields(idx.reshape(hp * wp, c), 2)
    i_ref[0] = crumbs.reshape(hp, wp, c // 4).astype(jnp.uint8)


def _unpool_bwd_kernel(i_ref, g_ref, o_ref, u_ref):
    unpool_store(u_ref, unpack_crumbs(i_ref[0]), widen(g_ref[0]))
    o_ref[0] = u_ref[...].astype(o_ref.dtype)


def maxpool_fwd_pallas(x: jnp.ndarray, *, interpret: Optional[bool] = None):
    """x: [N, H, W, C] (H, W even; C padded to 4) -> (pooled, packed idx)."""
    interpret = interpret_mode(interpret)
    n, h, w, c = x.shape
    cp = align_up(c, CRUMBS_PER_BYTE)
    xp = jnp.pad(x, ((0, 0), (0, 0), (0, 0), (0, cp - c)))
    xp = xp.reshape(n, h // 2, 2, w // 2, 2 * cp)      # free: row-major view
    y, idx = pl.pallas_call(
        _pool_fwd_kernel,
        grid=(n,),
        in_specs=[pl.BlockSpec((1, h // 2, 2, w // 2, 2 * cp),
                               lambda b: (b, 0, 0, 0, 0))],
        out_specs=[pl.BlockSpec((1, h // 2, w // 2, cp), lambda b: (b, 0, 0, 0)),
                   pl.BlockSpec((1, h // 2, w // 2, cp // 4), lambda b: (b, 0, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct((n, h // 2, w // 2, cp), x.dtype),
                   jax.ShapeDtypeStruct((n, h // 2, w // 2, cp // 4), jnp.uint8)],
        name="maxpool_fwd",
        interpret=interpret,
    )(xp)
    return y[..., :c], idx[..., :crumb_bytes(c)]


def unpool_bwd_pallas(packed: jnp.ndarray, g: jnp.ndarray, *,
                      interpret: Optional[bool] = None) -> jnp.ndarray:
    """packed: [N, H/2, W/2, ceil(C/4)], g: [N, H/2, W/2, C] -> [N, H, W, C]."""
    interpret = interpret_mode(interpret)
    n, hp, wp, c = g.shape
    cp = align_up(c, CRUMBS_PER_BYTE)
    gp = jnp.pad(g, ((0, 0), (0, 0), (0, 0), (0, cp - c)))
    ip = jnp.pad(packed, ((0, 0), (0, 0), (0, 0), (0, cp // 4 - packed.shape[-1])))
    out = pl.pallas_call(
        _unpool_bwd_kernel,
        grid=(n,),
        in_specs=[pl.BlockSpec((1, hp, wp, cp // 4), lambda b: (b, 0, 0, 0)),
                  pl.BlockSpec((1, hp, wp, cp), lambda b: (b, 0, 0, 0))],
        out_specs=pl.BlockSpec((1, 2 * hp, 2 * wp, cp), lambda b: (b, 0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((n, 2 * hp, 2 * wp, cp), g.dtype),
        scratch_shapes=[pltpu.VMEM((2 * hp, 2 * wp, cp), carrier(g.dtype))],
        name="maxpool_bwd",
        interpret=interpret,
    )(ip, gp)
    return out[..., :c]
