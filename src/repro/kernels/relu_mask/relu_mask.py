"""Fused ReLU + 1-bit packed-mask Pallas kernels (paper §III.D, Fig. 4).

The FPGA modifies values in-place in the on-chip output buffer and drops a
1-bit mask into BRAM.  On TPU: one VMEM-resident pass emits relu(x) and the
bit-packed mask together (no second HBM round-trip for the mask), and the BP
kernel fuses unpack + the method's gating rule into the gradient stream.

Bit packing inside the kernel: the [T, C] sign bits are contracted with a
[C, C/8] place-value matrix (entry 2^(c%8) where c//8 is the byte) — one
small MXU dot, exact because every operand and sum is an integer below
2^8.  A [T, C/8, 8] reshape would split the lane axis, which Mosaic cannot
lower.

:func:`unpack_bits` and :func:`gate_gradient` are IN-KERNEL helpers shared
with the fused conv/vmm backward kernels (conv2d/, vmm/), so the mask unpack
+ method gating runs as a prologue/epilogue inside those dots and the
gradient never round-trips HBM between the pointwise stage and the matmul.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import interpret_mode, mxu_precision, widen
from repro.kernels.tiling import LANE, align_up, mask_bytes, row_tiling


# ---------------------------------------------------------------------------
# in-kernel helpers (shared by the fused conv/vmm BP kernels)
# ---------------------------------------------------------------------------


def pack_fields(v: jnp.ndarray, width: int) -> jnp.ndarray:
    """[T, C] int32 fields of ``width`` bits -> [T, C*width/8] byte values.

    Byte j holds fields j*k .. j*k+k-1 (k = 8 // width), the first in the
    low bits — the layout of :mod:`repro.core.masks`.  Computed as one
    bf16 dot against an in-kernel place-value matrix: fields < 2^width
    and place values <= 2^7 are exact in bf16, and each byte's sum
    (<= 255) is exact in the f32 accumulator.  Returned as int32; the
    caller narrows to uint8 at the store.
    """
    per = 8 // width
    c = v.shape[-1]
    ci = jax.lax.broadcasted_iota(jnp.int32, (c, c // per), 0)
    bi = jax.lax.broadcasted_iota(jnp.int32, (c, c // per), 1)
    place = jnp.where(ci // per == bi, 1 << (width * (ci % per)), 0)
    packed = jnp.dot(v.astype(jnp.bfloat16), place.astype(jnp.bfloat16),
                     preferred_element_type=jnp.float32,
                     precision=mxu_precision(jnp.bfloat16))
    return packed.astype(jnp.int32)


def unpack_bits(packed: jnp.ndarray) -> jnp.ndarray:
    """[..., C/8] uint8 -> [..., C] bool — VPU shift/and unpack, no HBM."""
    shifts = jnp.arange(8, dtype=jnp.int32)
    bits = (packed.astype(jnp.int32)[..., None] >> shifts) & 1
    return bits.reshape(packed.shape[:-1]
                        + (packed.shape[-1] * 8,)).astype(jnp.bool_)


def gate_gradient(g: jnp.ndarray, mask_bits: Optional[jnp.ndarray],
                  method: str) -> jnp.ndarray:
    """The method's rectifier rule (paper Eq. 3-5) on a gradient block.

    ``mask_bits`` broadcasts against ``g`` (seed-batched grads carry leading
    axes the stored mask does not — the paper's mask-reuse amortization).
    The sign test runs on the 32-bit carrier (:func:`widen`); the result
    keeps ``g``'s dtype.
    """
    if method == "saliency":                         # Eq. 3: mask only
        return jnp.where(mask_bits, g, 0).astype(g.dtype)
    pos = widen(g) > 0                               # Eq. 4: sign only
    if method == "guided":                           # Eq. 5: sign AND mask
        pos = mask_bits & pos
    return jnp.where(pos, g, 0).astype(g.dtype)


# ---------------------------------------------------------------------------
# standalone kernels
# ---------------------------------------------------------------------------


def _relu_fwd_kernel(x_ref, y_ref, m_ref):
    x = widen(x_ref[...])
    y_ref[...] = jnp.maximum(x, 0).astype(y_ref.dtype)
    m_ref[...] = pack_fields((x > 0).astype(jnp.int32), 1).astype(jnp.uint8)


def _relu_bwd_kernel(m_ref, g_ref, r_ref, *, method: str):
    g = g_ref[...]
    if method == "deconvnet":               # no mask read at all
        r_ref[...] = gate_gradient(g, None, method)
        return
    t, c = g.shape
    m = unpack_bits(m_ref[...]).reshape(t, c)
    r_ref[...] = gate_gradient(g, m, method)


def _pad_rows_cols(a, tr, c_mult):
    r, c = a.shape
    rp, cp = align_up(r, tr), align_up(c, c_mult)
    return jnp.pad(a, ((0, rp - r), (0, cp - c))), rp, cp


def relu_fwd_pallas(x2d: jnp.ndarray, *, tr: Optional[int] = None,
                    interpret: Optional[bool] = None):
    """x2d: [R, C] -> (relu, packed mask [R, ceil(C/8)])."""
    interpret = interpret_mode(interpret)
    r, c = x2d.shape
    tr, _ = row_tiling(r, tr)
    xp, rp, cp = _pad_rows_cols(x2d, tr, LANE)
    y, m = pl.pallas_call(
        _relu_fwd_kernel,
        grid=(rp // tr,),
        in_specs=[pl.BlockSpec((tr, cp), lambda i: (i, 0))],
        out_specs=[pl.BlockSpec((tr, cp), lambda i: (i, 0)),
                   pl.BlockSpec((tr, cp // 8), lambda i: (i, 0))],
        out_shape=[jax.ShapeDtypeStruct((rp, cp), x2d.dtype),
                   jax.ShapeDtypeStruct((rp, cp // 8), jnp.uint8)],
        name="relu_mask_fwd",
        interpret=interpret,
    )(xp)
    return y[:r, :c], m[:r, :mask_bytes(c)]


def relu_bwd_pallas(packed: jnp.ndarray, g2d: jnp.ndarray, method: str, *,
                    tr: Optional[int] = None,
                    interpret: Optional[bool] = None) -> jnp.ndarray:
    """Masked gradient propagation; method is static (design-time config)."""
    interpret = interpret_mode(interpret)
    r, c = g2d.shape
    tr, _ = row_tiling(r, tr)
    gp, rp, cp = _pad_rows_cols(g2d, tr, LANE)
    mp = jnp.pad(packed, ((0, rp - r), (0, cp // 8 - packed.shape[1])))
    out = pl.pallas_call(
        functools.partial(_relu_bwd_kernel, method=method),
        grid=(rp // tr,),
        in_specs=[pl.BlockSpec((tr, cp // 8), lambda i: (i, 0)),
                  pl.BlockSpec((tr, cp), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((tr, cp), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rp, cp), g2d.dtype),
        name="relu_mask_bwd",
        interpret=interpret,
    )(mp, gp)
    return out[:r, :c]
