"""True int16 fixed-point FC matmul kernels (paper §IV: 16b datapath).

Same tiling as :mod:`vmm` — (TM, TK) x (TK, TN) VMEM blocks, the K grid
axis innermost — but with the FPGA's numeric contract: Q7.8 int16 inputs /
gradients, Q1.14 int16 weights, an **int32 output-stationary accumulator**
scratch carried across the K steps, and one round-half-up right-shift
requantization (+ symmetric saturation) at the flush.  Contract and NumPy
mirror in :mod:`repro.core.fixedpoint`.

The fused backward keeps the f32 kernel's structure: 1-bit mask unpack +
method gating as a prologue on the incoming int16 gradient (bits are
domain-free; gating is a select), optional epilogue gate on the outgoing
one — ONE ``pallas_call`` per FC layer backward step.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.fixedpoint import WGT_FRAC, dot_i16, requantize
from repro.kernels import (compiler_params, interpret_mode,
                           validate_bp_gates, widen)
from repro.kernels.tiling import vmm_tiling
from repro.kernels.relu_mask.relu_mask import gate_gradient, unpack_bits


def _mm_fxp_kernel(x_ref, w_ref, o_ref, acc_ref, *, k_steps: int, shift: int):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += dot_i16(x_ref[...], w_ref[...])

    @pl.when(pl.program_id(2) == k_steps - 1)
    def _flush():
        o_ref[...] = requantize(acc_ref[...], shift)


def vmm_fxp_pallas(x: jnp.ndarray, w: jnp.ndarray, *, shift: int = WGT_FRAC,
                   tm: Optional[int] = None, tk: Optional[int] = None,
                   tn: Optional[int] = None,
                   interpret: Optional[bool] = None) -> jnp.ndarray:
    """int16 [M, K] @ int16 [K, N] -> int16 [M, N], int32 accumulation.

    ``tm/tk/tn=None`` resolve through :func:`repro.kernels.tiling.vmm_tiling`
    (same policy as the f32 twin; int16 operands, int32 accumulator).
    """
    interpret = interpret_mode(interpret)
    assert x.dtype == jnp.int16 and w.dtype == jnp.int16, (x.dtype, w.dtype)
    m, k = x.shape
    k2, n = w.shape
    assert k == k2, (x.shape, w.shape)
    tm_, tk_, tn_, mp, kp, np_ = vmm_tiling(m, k, n, tm, tk, tn)
    xp = jnp.pad(x, ((0, mp - m), (0, kp - k)))
    wp = jnp.pad(w, ((0, kp - k), (0, np_ - n)))
    k_steps = kp // tk_

    out = pl.pallas_call(
        functools.partial(_mm_fxp_kernel, k_steps=k_steps, shift=shift),
        grid=(mp // tm_, np_ // tn_, k_steps),
        in_specs=[
            pl.BlockSpec((tm_, tk_), lambda i, j, s: (i, s)),
            pl.BlockSpec((tk_, tn_), lambda i, j, s: (s, j)),
        ],
        out_specs=pl.BlockSpec((tm_, tn_), lambda i, j, s: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mp, np_), jnp.int16),
        # i32 output-stationary accumulator, persists across the K grid axis
        scratch_shapes=[pltpu.VMEM((tm_, tn_), jnp.int32)],
        compiler_params=compiler_params(),
        name="vmm_fwd",
        interpret=interpret,
    )(xp, wp)
    return out[:m, :n]


# ---------------------------------------------------------------------------
# fused backward, int16: [mask gate] -> g @ W^T i32 dot -> requantize
# ---------------------------------------------------------------------------


def _mm_bwd_fused_fxp_kernel(*refs, k_steps: int, shift: int, method: str,
                             gate_in: bool, has_mask: bool, gate_out: bool,
                             has_omask: bool):
    it = iter(refs)
    g_ref, w_ref = next(it), next(it)
    m_ref = next(it) if has_mask else None
    om_ref = next(it) if has_omask else None
    o_ref, acc_ref = next(it), next(it)

    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    g = widen(g_ref[0])
    if gate_in:                                         # prologue: Eq. 3-5
        m = unpack_bits(m_ref[...]) if has_mask else None
        g = gate_gradient(g, m, method)
    acc_ref[...] += dot_i16(g, w_ref[...])

    @pl.when(pl.program_id(2) == k_steps - 1)
    def _flush():
        out = requantize(acc_ref[...], shift)
        if gate_out:                                    # epilogue: prev ReLU
            om = unpack_bits(om_ref[...]) if has_omask else None
            out = gate_gradient(out, om, method)
        o_ref[0] = out


def vmm_bwd_fused_fxp_pallas(
        g: jnp.ndarray, w: jnp.ndarray, *,
        relu_mask: Optional[jnp.ndarray] = None,
        gate: Optional[bool] = None,
        method: str = "saliency",
        out_relu_mask: Optional[jnp.ndarray] = None,
        out_gate: Optional[bool] = None,
        shift: int = WGT_FRAC, tk: Optional[int] = None,
        tn: Optional[int] = None,
        interpret: Optional[bool] = None) -> jnp.ndarray:
    """int16 twin of :func:`vmm.vmm_bwd_fused_pallas` — same fused dataflow
    and argument contract, Q7.8 gradients / Q1.14 weights, ONE pallas_call
    per FC layer backward step."""
    interpret = interpret_mode(interpret)
    assert g.dtype == jnp.int16 and w.dtype == jnp.int16, (g.dtype, w.dtype)
    gate, out_gate = validate_bp_gates(method, gate, relu_mask, out_gate,
                                       out_relu_mask)
    seeded = g.ndim == 3
    if not seeded:
        g = g[None]
    s, m, k = g.shape
    k2, n = w.shape
    assert k == k2, (g.shape, w.shape)

    _, tk_, tn_, mp, kp, np_ = vmm_tiling(m, k, n, m, tk, tn)
    k_steps = kp // tk_

    gp = jnp.pad(g, ((0, 0), (0, mp - m), (0, kp - k)))
    wp = jnp.pad(w, ((0, kp - k2), (0, np_ - n)))
    in_specs = [
        pl.BlockSpec((1, mp, tk_), lambda si, j, st: (si, 0, st)),
        pl.BlockSpec((tk_, tn_), lambda si, j, st: (st, j)),
    ]
    operands = [gp, wp]
    has_mask = relu_mask is not None
    if has_mask:
        mpk = jnp.pad(relu_mask,
                      ((0, mp - m), (0, kp // 8 - relu_mask.shape[-1])))
        in_specs.append(pl.BlockSpec((mp, tk_ // 8),
                                     lambda si, j, st: (0, st)))
        operands.append(mpk)
    has_omask = out_relu_mask is not None
    if has_omask:
        ompk = jnp.pad(out_relu_mask,
                       ((0, mp - m), (0, np_ // 8 - out_relu_mask.shape[-1])))
        in_specs.append(pl.BlockSpec((mp, tn_ // 8),
                                     lambda si, j, st: (0, j)))
        operands.append(ompk)

    out = pl.pallas_call(
        functools.partial(
            _mm_bwd_fused_fxp_kernel, k_steps=k_steps, shift=shift,
            method=method, gate_in=gate, has_mask=has_mask,
            gate_out=out_gate, has_omask=has_omask),
        grid=(s, np_ // tn_, k_steps),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, mp, tn_), lambda si, j, st: (si, 0, j)),
        out_shape=jax.ShapeDtypeStruct((s, mp, np_), jnp.int16),
        scratch_shapes=[pltpu.VMEM((mp, tn_), jnp.int32)],
        compiler_params=compiler_params(),
        name="vmm_bwd",
        interpret=interpret,
    )(*operands)
    out = out[:, :m, :n]
    return out if seeded else out[0]
