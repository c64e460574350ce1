"""Tiled single-dot convolution kernels (paper §III.B, Fig. 4-6) for TPU.

FPGA -> TPU mapping:

  * DRAM -> BRAM tile loads over AXI  ==>  HBM -> VMEM blocks via BlockSpec.
  * N_oh x N_ow unrolled MAC array    ==>  ONE MXU contraction per tile:
    the K*K taps of the already-loaded padded block are gathered in VMEM
    (im2col) into a [H*W, K*K*Cin] patch matrix and contracted against the
    [K*K*Cin, Tco] flattened kernel — a single MXU-shaped dot instead of
    K^2 skinny [H*W, Cin] dots, so the MXU sees one deep contraction and
    the weights stream through once per tile.
  * Output-stationary accumulation    ==>  f32 accumulator in VMEM registers,
    written once per output tile.

Because the paper targets edge CNNs (CIFAR-scale feature maps), a whole
padded feature map fits easily in VMEM (34*34*128*4B = 0.6 MB << 16 MB), so
we tile over (batch, Cout) and keep H/W un-tiled — the TPU analogue of the
FPGA's "maximally use on-chip resources" rule.  Cout tiles are 128-aligned
for the MXU lane width; Cin is zero-padded to the sublane multiple.

:func:`conv2d_bwd_fused_pallas` is the fused BP dataflow: the 2-bit unpool
scatter and the 1-bit ReLU mask gating run INSIDE the conv-BP pallas_call as
prologues on the incoming gradient (optionally a second gate as epilogue on
the outgoing one), so a CNN layer's whole backward step is one kernel and
the gradient never touches HBM between the pointwise stages and the dot.
A leading seeds axis S folds into the sublane dimension of the patch matrix
([S*H*W, K*K*C]), so explaining S classes shares one mask/index load per
tile — the paper's mask-reuse amortization.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import (carrier, compiler_params, interpret_mode,
                           mxu_precision, validate_bp_gates, widen)
from repro.kernels.tiling import SUBLANE, align_up, batch_tiling, cout_tiling
from repro.kernels.pool.pool import unpack_crumbs, unpool_store
from repro.kernels.relu_mask.relu_mask import gate_gradient, unpack_bits


def _im2col_dot(xpad, K: int, H: int, W: int, wmat):
    """[S, H+2p, W+2p, C] -> one [S*H*W, K*K*C] @ [K*K*C, T] f32 dot."""
    s, _, _, c = xpad.shape
    cols = [xpad[:, i:i + H, j:j + W, :].reshape(s * H * W, c)
            for i in range(K) for j in range(K)]
    patches = jnp.concatenate(cols, axis=1)              # [S*H*W, K*K*C]
    acc = jnp.dot(patches, wmat, preferred_element_type=jnp.float32,
                  precision=mxu_precision(patches.dtype))
    return acc.reshape(s, H, W, wmat.shape[-1])


def _conv_kernel(x_ref, w_ref, o_ref, *, K: int, H: int, W: int):
    """One (batch, cout-tile) grid cell: full-map single-dot conv."""
    cin = x_ref.shape[-1]
    tco = o_ref.shape[-1]
    wmat = w_ref[...].reshape(K * K * cin, tco)
    o_ref[...] = _im2col_dot(x_ref[...], K, H, W, wmat).astype(o_ref.dtype)


def conv2d_pallas(x: jnp.ndarray, w: jnp.ndarray, *,
                  co_tile: Optional[int] = None,
                  bn: Optional[int] = None,
                  interpret: Optional[bool] = None) -> jnp.ndarray:
    """[N, H, W, Cin] x [K, K, Cin, Cout] -> [N, H, W, Cout], stride 1, SAME.

    ``co_tile=None`` resolves through
    :func:`repro.kernels.tiling.cout_tiling` (planner tiles override the
    default policy).  ``bn`` is the batch block — examples per grid cell
    (default 1; folded forwards pass the
    :func:`repro.kernels.tiling.fold_batch_tile` policy so the weight
    stream and launch overhead amortize over the fan-out).  The kernel body
    is block-size agnostic: the im2col patch matrix simply grows its
    sublane dim to ``bn * H * W``.
    """
    interpret = interpret_mode(interpret)
    n, h, ww, cin = x.shape
    k, _, _, cout = w.shape
    p = (k - 1) // 2

    # Zero-pad: batch to block multiple, spatial halo (SAME), Cin to
    # sublane multiple, Cout to tile.
    bn, n_p = batch_tiling(n, bn)
    cin_p = align_up(cin, SUBLANE)
    tco, cout_p = cout_tiling(cout, co_tile)
    xp = jnp.pad(x, ((0, n_p - n), (p, p), (p, p), (0, cin_p - cin)))
    wp = jnp.pad(w, ((0, 0), (0, 0), (0, cin_p - cin), (0, cout_p - cout)))

    grid = (n_p // bn, cout_p // tco)
    out = pl.pallas_call(
        functools.partial(_conv_kernel, K=k, H=h, W=ww),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bn, h + 2 * p, ww + 2 * p, cin_p),
                         lambda b, c: (b, 0, 0, 0)),
            pl.BlockSpec((k, k, cin_p, tco), lambda b, c: (0, 0, 0, c)),
        ],
        out_specs=pl.BlockSpec((bn, h, ww, tco), lambda b, c: (b, 0, 0, c)),
        out_shape=jax.ShapeDtypeStruct((n_p, h, ww, cout_p), x.dtype),
        compiler_params=compiler_params(),
        name="conv2d_fwd",
        interpret=interpret,
    )(xp, wp)
    return out[:n, ..., :cout]


# ---------------------------------------------------------------------------
# fused backward: [unpool] -> [mask gate] -> conv-BP dot -> [epilogue gate]
# ---------------------------------------------------------------------------


def _conv_bwd_fused_kernel(*refs, K: int, H: int, W: int, method: str,
                           has_pool: bool, gate_in: bool, has_mask: bool,
                           gate_out: bool, has_omask: bool):
    it = iter(refs)
    g_ref, w_ref = next(it), next(it)
    i_ref = next(it) if has_pool else None
    m_ref = next(it) if has_mask else None
    om_ref = next(it) if has_omask else None
    o_ref = next(it)
    u_ref = next(it) if has_pool else None              # 32-bit unpool scratch

    p = (K - 1) // 2
    c = g_ref.shape[-1]
    s = g_ref.shape[0]
    tco = o_ref.shape[-1]

    g = g_ref[:, 0]                                     # [S, Hg, Wg, C]
    if has_pool:                                        # prologue 1: unpool
        unpool_store(u_ref, unpack_crumbs(i_ref[0]), widen(g))
        g = u_ref[...].astype(g.dtype)                  # -> [S, H, W, C]
    if gate_in:                                         # prologue 2: Eq. 3-5
        m = unpack_bits(m_ref[0]) if has_mask else None
        g = gate_gradient(g, m, method)

    # halo-pad in VMEM, then the single im2col dot (flipped-transpose conv)
    gp = jnp.pad(g, ((0, 0), (p, p), (p, p), (0, 0)))
    out = _im2col_dot(gp, K, H, W, w_ref[...].reshape(K * K * c, tco))

    if gate_out:                                        # epilogue: prev ReLU
        om = unpack_bits(om_ref[0]) if has_omask else None
        out = gate_gradient(out, om, method)
    o_ref[...] = out.reshape(s, 1, H, W, tco).astype(o_ref.dtype)


def conv2d_bwd_fused_pallas(
        g: jnp.ndarray, wt: jnp.ndarray, *,
        pool_idx: Optional[jnp.ndarray] = None,
        relu_mask: Optional[jnp.ndarray] = None,
        gate: Optional[bool] = None,
        method: str = "saliency",
        out_relu_mask: Optional[jnp.ndarray] = None,
        out_gate: Optional[bool] = None,
        co_tile: Optional[int] = None,
        interpret: Optional[bool] = None) -> jnp.ndarray:
    """One pallas_call for a conv layer's whole backward step.

    ``g``:        grads w.r.t. the layer output — [N, Hg, Wg, C] or
                  seed-batched [S, N, Hg, Wg, C] (Hg = H/2 when pooled).
    ``wt``:       flip-transposed kernel [K, K, C, Cout'] (forward
                  ``ref.flip_transpose(w)``; Cout' is the forward Cin).
    ``pool_idx``: [N, Hg, Wg, ceil(C/4)] packed 2-bit argmax (None: no pool).
    ``relu_mask``: [N, H, W, ceil(C/8)] packed 1-bit mask of the layer's own
                  ReLU.  ``gate`` forces the rectifier rule on/off — pass
                  ``gate=True`` with no mask for deconvnet (Eq. 4 reads only
                  the gradient sign).
    ``out_relu_mask``/``out_gate``: same, applied as an EPILOGUE on the
                  outgoing dx (the PREVIOUS layer's rectifier), [N, H, W,
                  ceil(Cout'/8)].
    Masks/indices carry no seeds axis: all S seeds share one stored residual
    load per tile (the paper's mask-reuse amortization).
    """
    interpret = interpret_mode(interpret)
    gate, out_gate = validate_bp_gates(method, gate, relu_mask, out_gate,
                                       out_relu_mask)
    seeded = g.ndim == 5
    if not seeded:
        g = g[None]
    s, n, hg, wg, c = g.shape
    k, _, cw, cout = wt.shape
    has_pool = pool_idx is not None
    h, w_sp = (2 * hg, 2 * wg) if has_pool else (hg, wg)

    cp = align_up(c, SUBLANE)                # contraction channels (fwd Cout)
    # cout_tiling is sublane-aligned, as the epilogue mask bytes (tco // 8
    # per pixel) require.
    tco, cout_p = cout_tiling(cout, co_tile)
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
            _conv_bwd_fused_kernel, K=k, H=h, W=w_sp, method=method,
            has_pool=has_pool, gate_in=gate, has_mask=has_mask,
            gate_out=out_gate, has_omask=has_omask),
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((s, 1, h, w_sp, tco),
                               lambda b, co: (0, b, 0, 0, co)),
        out_shape=jax.ShapeDtypeStruct((s, n, h, w_sp, cout_p), g.dtype),
        scratch_shapes=([pltpu.VMEM((s, h, w_sp, cp), carrier(g.dtype))]
                        if has_pool else []),
        compiler_params=compiler_params(),
        name="conv2d_bwd",
        interpret=interpret,
    )(*operands)
    out = out[..., :cout]
    return out if seeded else out[0]
