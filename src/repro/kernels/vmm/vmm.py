"""Tiled matmul kernel for FC layers (paper §III.C) for TPU.

FPGA -> TPU mapping: the input vector / weight-matrix tiles in on-chip
buffers become (TM, TK) x (TK, TN) VMEM blocks; the unrolled MAC loop
becomes one MXU dot per grid step; output-stationary accumulation is an f32
VMEM scratch accumulated across the K grid dimension (the innermost,
"arbitrary" axis), flushed once per (M, N) tile.

The BP phase reuses this kernel on a transposed weight view — the paper's
"buffers loaded in a transpose manner from DRAM" (§III.E) — see ops.py.

:func:`vmm_bwd_fused_pallas` is the fused BP variant: the 1-bit ReLU mask
unpack + method gating runs INSIDE the matmul kernel as a prologue on the
incoming gradient (and optionally as an epilogue on the outgoing one), so an
FC layer's backward step is one pallas_call and the gated gradient never
round-trips HBM.  A leading seeds axis S folds into the grid so explaining
S classes shares one stored mask (the paper's mask-reuse amortization).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import (compiler_params, interpret_mode, mxu_precision,
                           validate_bp_gates)
from repro.kernels.tiling import vmm_tiling
from repro.kernels.relu_mask.relu_mask import gate_gradient, unpack_bits


def _mm_kernel(x_ref, w_ref, o_ref, acc_ref, *, k_steps: int):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...]
    acc_ref[...] += jnp.dot(x, w_ref[...], preferred_element_type=jnp.float32,
                            precision=mxu_precision(x.dtype))

    @pl.when(pl.program_id(2) == k_steps - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def vmm_pallas(x: jnp.ndarray, w: jnp.ndarray, *, tm: Optional[int] = None,
               tk: Optional[int] = None, tn: Optional[int] = None,
               interpret: Optional[bool] = None) -> jnp.ndarray:
    """[M, K] @ [K, N] -> [M, N], MXU-aligned VMEM tiles, f32 accumulate.

    ``tm/tk/tn=None`` resolve through :func:`repro.kernels.tiling.vmm_tiling`
    (planner-provided tiles override the defaults); K/N padding is always
    lane-aligned, never the raw dim.
    """
    interpret = interpret_mode(interpret)
    m, k = x.shape
    k2, n = w.shape
    assert k == k2, (x.shape, w.shape)
    tm_, tk_, tn_, mp, kp, np_ = vmm_tiling(m, k, n, tm, tk, tn)
    xp = jnp.pad(x, ((0, mp - m), (0, kp - k)))
    wp = jnp.pad(w, ((0, kp - k), (0, np_ - n)))
    k_steps = kp // tk_

    out = pl.pallas_call(
        functools.partial(_mm_kernel, k_steps=k_steps),
        grid=(mp // tm_, np_ // tn_, k_steps),
        in_specs=[
            pl.BlockSpec((tm_, tk_), lambda i, j, s: (i, s)),
            pl.BlockSpec((tk_, tn_), lambda i, j, s: (s, j)),
        ],
        out_specs=pl.BlockSpec((tm_, tn_), lambda i, j, s: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mp, np_), x.dtype),
        # f32 output-stationary accumulator, persists across the K grid axis
        scratch_shapes=[pltpu.VMEM((tm_, tn_), jnp.float32)],
        compiler_params=compiler_params(),
        name="vmm_fwd",
        interpret=interpret,
    )(xp, wp)
    return out[:m, :n]


# ---------------------------------------------------------------------------
# fused backward: [mask gate] -> g @ W^T dot -> [epilogue gate]
# ---------------------------------------------------------------------------


def _mm_bwd_fused_kernel(*refs, k_steps: int, method: str, gate_in: bool,
                         has_mask: bool, gate_out: bool, has_omask: bool):
    it = iter(refs)
    g_ref, w_ref = next(it), next(it)
    m_ref = next(it) if has_mask else None
    om_ref = next(it) if has_omask else None
    o_ref, acc_ref = next(it), next(it)

    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    g = g_ref[0]
    if gate_in:                                         # prologue: Eq. 3-5
        m = unpack_bits(m_ref[...]) if has_mask else None
        g = gate_gradient(g, m, method)
    acc_ref[...] += jnp.dot(g, w_ref[...], preferred_element_type=jnp.float32,
                            precision=mxu_precision(g.dtype))

    @pl.when(pl.program_id(2) == k_steps - 1)
    def _flush():
        out = acc_ref[...]
        if gate_out:                                    # epilogue: prev ReLU
            om = unpack_bits(om_ref[...]) if has_omask else None
            out = gate_gradient(out, om, method)
        o_ref[0] = out.astype(o_ref.dtype)


def vmm_bwd_fused_pallas(
        g: jnp.ndarray, w: jnp.ndarray, *,
        relu_mask: Optional[jnp.ndarray] = None,
        gate: Optional[bool] = None,
        method: str = "saliency",
        out_relu_mask: Optional[jnp.ndarray] = None,
        out_gate: Optional[bool] = None,
        tk: Optional[int] = None, tn: Optional[int] = None,
        interpret: Optional[bool] = None) -> jnp.ndarray:
    """One pallas_call for an FC layer's whole backward step.

    ``g``:  [M, K] or seed-batched [S, M, K] grads w.r.t. the FC output.
    ``w``:  [K, N] — the TRANSPOSED weight view (caller passes ``W.T``).
    ``relu_mask``: [M, ceil(K/8)] packed 1-bit mask of the layer's ReLU;
    ``gate=True`` with no mask selects the deconvnet rule (gradient sign
    only).  ``out_relu_mask``/``out_gate``: epilogue on the outgoing dx,
    [M, ceil(N/8)].  Masks carry no seeds axis — shared across S.
    ``tk/tn=None`` resolve through :func:`repro.kernels.tiling.vmm_tiling`.
    """
    interpret = interpret_mode(interpret)
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
            _mm_bwd_fused_kernel, k_steps=k_steps, method=method,
            gate_in=gate, has_mask=has_mask, gate_out=out_gate,
            has_omask=has_omask),
        grid=(s, np_ // tn_, k_steps),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, mp, tn_), lambda si, j, st: (si, 0, j)),
        out_shape=jax.ShapeDtypeStruct((s, mp, np_), g.dtype),
        scratch_shapes=[pltpu.VMEM((mp, tn_), jnp.float32)],
        compiler_params=compiler_params(),
        name="vmm_bwd",
        interpret=interpret,
    )(*operands)
    out = out[:, :m, :n]
    return out if seeded else out[0]
