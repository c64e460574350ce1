"""True int16 fixed-point conv kernels (paper §IV: 16b datapath end-to-end).

Same tiling / single-dot im2col dataflow as :mod:`conv2d`, but the numeric
contract is the FPGA's: **Q7.8 int16** feature maps and gradients,
**Q1.14 int16** weights, one **int32 MXU contraction** per tile, and a
single round-half-up right-shift requantization (+ symmetric saturation)
narrowing the accumulator back to the 16-bit datapath — see
:mod:`repro.core.fixedpoint` for the contract and the NumPy mirror.

The fused backward keeps the f32 kernel's structure exactly: the 2-bit
unpool scatter and the 1-bit mask gating run unchanged as prologues on the
incoming int16 gradient (masks are domain-free bits; gating is a select),
then the flipped-transpose conv dot accumulates in int32 and requantizes
once.  One ``pallas_call`` per layer backward, int16 end to end.
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
from repro.kernels.tiling import SUBLANE, align_up, cout_tiling
from repro.kernels.pool.pool import unpack_crumbs, unpool_store
from repro.kernels.relu_mask.relu_mask import gate_gradient, unpack_bits


def _im2col_dot_i32(xpad, K: int, H: int, W: int, wmat):
    """[S, H+2p, W+2p, C] int16 values -> [S, H, W, T] int32 single-dot
    im2col; the contraction is :func:`dot_i16` (int8 MXU digits)."""
    s, _, _, c = xpad.shape
    cols = [xpad[:, i:i + H, j:j + W, :].reshape(s * H * W, c)
            for i in range(K) for j in range(K)]
    patches = jnp.concatenate(cols, axis=1)              # [S*H*W, K*K*C]
    return dot_i16(patches, wmat).reshape(s, H, W, wmat.shape[-1])


def _conv_fxp_kernel(x_ref, w_ref, o_ref, *, K: int, H: int, W: int,
                     shift: int):
    cin = x_ref.shape[-1]
    tco = o_ref.shape[-1]
    wmat = w_ref[...].reshape(K * K * cin, tco)
    acc = _im2col_dot_i32(widen(x_ref[...]), K, H, W, wmat)
    o_ref[...] = requantize(acc, shift)


def conv2d_fxp_pallas(x: jnp.ndarray, w: jnp.ndarray, *,
                      shift: int = WGT_FRAC, co_tile: Optional[int] = None,
                      interpret: Optional[bool] = None) -> jnp.ndarray:
    """int16 [N, H, W, Cin] x int16 [K, K, Cin, Cout] -> int16, stride 1, SAME.

    ``shift`` is the weight fraction width: products carry scale
    2^(8+shift) and one requantization returns the Q7.8 activation grid.
    """
    interpret = interpret_mode(interpret)
    assert x.dtype == jnp.int16 and w.dtype == jnp.int16, (x.dtype, w.dtype)
    n, h, ww, cin = x.shape
    k, _, _, cout = w.shape
    p = (k - 1) // 2

    cin_p = align_up(cin, SUBLANE)
    tco, cout_p = cout_tiling(cout, co_tile)
    xp = jnp.pad(x, ((0, 0), (p, p), (p, p), (0, cin_p - cin)))
    wp = jnp.pad(w, ((0, 0), (0, 0), (0, cin_p - cin), (0, cout_p - cout)))

    grid = (n, cout_p // tco)
    out = pl.pallas_call(
        functools.partial(_conv_fxp_kernel, K=k, H=h, W=ww, shift=shift),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, h + 2 * p, ww + 2 * p, cin_p),
                         lambda b, c: (b, 0, 0, 0)),
            pl.BlockSpec((k, k, cin_p, tco), lambda b, c: (0, 0, 0, c)),
        ],
        out_specs=pl.BlockSpec((1, h, ww, tco), lambda b, c: (b, 0, 0, c)),
        out_shape=jax.ShapeDtypeStruct((n, h, ww, cout_p), jnp.int16),
        compiler_params=compiler_params(),
        name="conv2d_fwd",
        interpret=interpret,
    )(xp, wp)
    return out[..., :cout]


# ---------------------------------------------------------------------------
# fused backward, int16: [unpool] -> [mask gate] -> i32 dot -> requantize
# ---------------------------------------------------------------------------


def _conv_bwd_fused_fxp_kernel(*refs, K: int, H: int, W: int, method: str,
                               shift: int, has_pool: bool, gate_in: bool,
                               has_mask: bool, gate_out: bool,
                               has_omask: bool):
    it = iter(refs)
    g_ref, w_ref = next(it), next(it)
    i_ref = next(it) if has_pool else None
    m_ref = next(it) if has_mask else None
    om_ref = next(it) if has_omask else None
    o_ref = next(it)
    u_ref = next(it) if has_pool else None              # int32 unpool scratch

    p = (K - 1) // 2
    c = g_ref.shape[-1]
    s = g_ref.shape[0]
    tco = o_ref.shape[-1]

    g = widen(g_ref[:, 0])                              # [S, Hg, Wg, C] i32
    if has_pool:                                        # prologue 1: unpool
        unpool_store(u_ref, unpack_crumbs(i_ref[0]), g)
        g = u_ref[...]                                  # -> [S, H, W, C]
    if gate_in:                                         # prologue 2: Eq. 3-5
        m = unpack_bits(m_ref[0]) if has_mask else None
        g = gate_gradient(g, m, method)

    gp = jnp.pad(g, ((0, 0), (p, p), (p, p), (0, 0)))
    out = requantize(
        _im2col_dot_i32(gp, K, H, W, w_ref[...].reshape(K * K * c, tco)),
        shift)

    if gate_out:                                        # epilogue: prev ReLU
        om = unpack_bits(om_ref[0]) if has_omask else None
        out = gate_gradient(out, om, method)
    o_ref[...] = out.reshape(s, 1, H, W, tco)


def conv2d_bwd_fused_fxp_pallas(
        g: jnp.ndarray, wt: jnp.ndarray, *,
        pool_idx: Optional[jnp.ndarray] = None,
        relu_mask: Optional[jnp.ndarray] = None,
        gate: Optional[bool] = None,
        method: str = "saliency",
        out_relu_mask: Optional[jnp.ndarray] = None,
        out_gate: Optional[bool] = None,
        shift: int = WGT_FRAC, co_tile: Optional[int] = None,
        interpret: Optional[bool] = None) -> jnp.ndarray:
    """int16 twin of :func:`conv2d.conv2d_bwd_fused_pallas` — same fused
    dataflow and argument contract, Q7.8 gradients / Q1.14 weights, ONE
    pallas_call per conv layer backward step."""
    interpret = interpret_mode(interpret)
    assert g.dtype == jnp.int16 and wt.dtype == jnp.int16, (g.dtype, wt.dtype)
    gate, out_gate = validate_bp_gates(method, gate, relu_mask, out_gate,
                                       out_relu_mask)
    seeded = g.ndim == 5
    if not seeded:
        g = g[None]
    s, n, hg, wg, c = g.shape
    k, _, cw, cout = wt.shape
    has_pool = pool_idx is not None
    h, w_sp = (2 * hg, 2 * wg) if has_pool else (hg, wg)

    cp = align_up(c, SUBLANE)
    tco, cout_p = cout_tiling(cout, co_tile)   # sublane-aligned (mask bytes)
    gp = jnp.pad(g, ((0, 0),) * 4 + ((0, cp - c),))
    wp = jnp.pad(wt, ((0, 0), (0, 0), (0, cp - cw), (0, cout_p - cout)))

    grid = (n, cout_p // tco)
    in_specs = [
        pl.BlockSpec((s, 1, hg, wg, cp), lambda b, co: (0, b, 0, 0, 0)),
        pl.BlockSpec((k, k, cp, tco), lambda b, co: (0, 0, 0, co)),
    ]
    operands = [gp, wp]

    if has_pool:
        ip = jnp.pad(pool_idx,
                     ((0, 0),) * 3 + ((0, cp // 4 - pool_idx.shape[-1]),))
        in_specs.append(pl.BlockSpec((1, hg, wg, cp // 4),
                                     lambda b, co: (b, 0, 0, 0)))
        operands.append(ip)
    has_mask = relu_mask is not None
    if has_mask:
        mp = jnp.pad(relu_mask,
                     ((0, 0),) * 3 + ((0, cp // 8 - relu_mask.shape[-1]),))
        in_specs.append(pl.BlockSpec((1, h, w_sp, cp // 8),
                                     lambda b, co: (b, 0, 0, 0)))
        operands.append(mp)
    has_omask = out_relu_mask is not None
    if has_omask:
        omp = jnp.pad(out_relu_mask,
                      ((0, 0),) * 3
                      + ((0, cout_p // 8 - out_relu_mask.shape[-1]),))
        in_specs.append(pl.BlockSpec((1, h, w_sp, tco // 8),
                                     lambda b, co: (b, 0, 0, co)))
        operands.append(omp)

    out = pl.pallas_call(
        functools.partial(
            _conv_bwd_fused_fxp_kernel, K=k, H=h, W=w_sp, method=method,
            shift=shift, has_pool=has_pool, gate_in=gate, has_mask=has_mask,
            gate_out=out_gate, has_omask=has_omask),
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((s, 1, h, w_sp, tco),
                               lambda b, co: (0, b, 0, 0, co)),
        out_shape=jax.ShapeDtypeStruct((s, n, h, w_sp, cout_p), jnp.int16),
        scratch_shapes=([pltpu.VMEM((s, h, w_sp, cp), jnp.int32)]
                        if has_pool else []),
        compiler_params=compiler_params(),
        name="conv2d_bwd",
        interpret=interpret,
    )(*operands)
    out = out[..., :cout]
    return out if seeded else out[0]
