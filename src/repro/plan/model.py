"""Analytic footprint/cost model per Pallas kernel family.

The paper's Table-style resource analysis, in code: for a candidate tile
shape, how many on-chip bytes does ONE grid cell of the kernel hold
(input/output blocks, packed residual blocks, accumulator scratch, and the
im2col patch matrix the conv kernels materialize in VMEM), how many HBM
bytes does the whole call move, and what fraction of the MAC array do the
dot shapes occupy.  The planner rejects any candidate whose
:attr:`Footprint.vmem_bytes` exceeds the profile budget and ranks the rest
by :meth:`Footprint.est_time_s` — a two-term roofline
(max of compute time at the utilization-derated peak and memory time at the
profile bandwidth).

Every formula mirrors the corresponding wrapper in :mod:`repro.kernels`
exactly — same padding helpers, same blocks — so "analytic footprint fits"
is a statement about the real kernel, not an idealization.

dtype widths: f32 -> 4 B operands / f32 accumulator; bf16 -> 2 B / f32;
fxp16 (true int16, paper §IV) -> 2 B / int32.

On-chip layout: the conv families take a ``layout``.  ``None`` counts
flat bytes (the edge targets' BRAM).  A ``(sublane, lane)`` pair models
TPU VMEM, which stores an array in whole tiles: the last dim rounds up to
lanes and the one before it to sublanes (a 32-channel map occupies 128
lanes).  Pallas also double-buffers every pipelined in/out block there;
Mosaic materializes the unpooled and gated gradient, and fxp16 kernels
compute on the int32 carrier and split the im2col dot into int8 digit
planes (``core.fixedpoint.dot_i16``).  Checked against the TPU compiler:
``tests/test_tpu_compile.py`` compiles the paper CNN's conv kernels for a
v5e under exactly the modelled bytes.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Optional, Sequence, Tuple

from repro.kernels.tiling import (BITS_PER_BYTE, CRUMBS_PER_BYTE, SUBLANE,
                                  align_up, cout_tiling, vmm_tiling)

#: operand element bytes per precision.
ELT_BYTES = {"f32": 4, "bf16": 2, "fxp16": 2}
#: accumulator element bytes (f32 for floats, int32 for fxp16).
ACC_BYTES = {"f32": 4, "bf16": 4, "fxp16": 4}


def _elt(precision: str) -> int:
    try:
        return ELT_BYTES[precision]
    except KeyError:
        raise ValueError(f"precision={precision!r} not in "
                         f"{tuple(ELT_BYTES)}") from None


@dataclass(frozen=True)
class Footprint:
    """Resource usage of one kernel call under a candidate tile shape."""

    #: peak on-chip bytes of ONE grid cell (blocks + scratch).
    vmem_bytes: int
    #: total HBM bytes moved by the whole call (all grid cells).
    hbm_bytes: int
    #: total MACs * 2 of the padded computation.
    flops: int
    #: fraction of the MAC array the tile's dot shapes occupy (0..1].
    mxu_util: float

    def fits(self, profile) -> bool:
        """Does one grid cell fit the profile's on-chip budget?"""
        return self.vmem_bytes <= profile.vmem_bytes

    def est_time_s(self, profile) -> float:
        """Two-term roofline estimate: compute at the derated peak vs
        HBM traffic at the profile bandwidth."""
        compute = self.flops / (profile.mxu_tflops * 1e12
                                * max(self.mxu_util, 1e-3))
        memory = self.hbm_bytes / (profile.hbm_gbps * 1e9)
        return max(compute, memory)


def _vmem(shape: Sequence[int], elt: int,
          layout: Optional[Tuple[int, int]]) -> int:
    """On-chip bytes of an array of ``shape`` under ``layout`` (see the
    module docstring): flat when None, else its last two dims padded to
    whole (sublane, lane) tiles."""
    dims = list(shape)
    if layout is not None:
        sub, lane = layout
        dims[-1] = align_up(dims[-1], lane)
        if len(dims) > 1:
            dims[-2] = align_up(dims[-2], sub)
    return math.prod(dims) * elt


def _im2col_work(rows: int, depth: int, tco: int, precision: str,
                 layout: Optional[Tuple[int, int]]) -> int:
    """In-kernel values of the single-dot im2col: the [rows, depth] patch
    matrix and the [rows, tco] accumulator.  On tiled VMEM an fxp16 kernel
    builds the patches on the int32 carrier and adds ``dot_i16``'s two int8
    digit planes and four int32 digit products."""
    elt, acc = _elt(precision), ACC_BYTES[precision]
    if layout is None or precision != "fxp16":
        return (_vmem((rows, depth), elt, layout)
                + _vmem((rows, tco), acc, layout))
    return (_vmem((rows, depth), 4, layout)
            + 2 * _vmem((rows, depth), 1, layout)
            + 5 * _vmem((rows, tco), 4, layout))


def _dot_util(sub_rows: int, depth: int, lanes: int, mxu: int) -> float:
    """MAC-array occupancy proxy of an [R, D] @ [D, L] tile dot."""
    return (min(1.0, sub_rows / mxu) * min(1.0, depth / mxu)
            * min(1.0, lanes / mxu))


# ---------------------------------------------------------------------------
# conv2d family (single-dot im2col; repro.kernels.conv2d)
# ---------------------------------------------------------------------------


def conv2d_fwd_footprint(n: int, h: int, w: int, k: int, cin: int,
                         cout: int, co_tile: int, precision: str = "f32",
                         mxu: int = 128,
                         layout: Optional[Tuple[int, int]] = None
                         ) -> Footprint:
    """One (batch, cout-tile) grid cell of :func:`conv2d_pallas`.

    VMEM: padded input block + weight block + the [H*W, K*K*Cin] im2col
    patch matrix gathered in VMEM + the f32/int32 accumulator + the output
    block.  HBM: the input block reloads once per cout tile.
    """
    elt = _elt(precision)
    p = (k - 1) // 2
    cin_p = align_up(cin, SUBLANE)
    tco, cout_p = cout_tiling(cout, co_tile)
    x_blk = (h + 2 * p) * (w + 2 * p) * cin_p * elt
    w_blk = k * k * cin_p * tco * elt
    tiles = cout_p // tco
    bufs = 1 if layout is None else 2
    blocks = (_vmem((h + 2 * p, w + 2 * p, cin_p), elt, layout)
              + _vmem((k * k, cin_p, tco), elt, layout)
              + _vmem((h, w, tco), elt, layout))
    work = _im2col_work(h * w, k * k * cin_p, tco, precision, layout)
    if layout is not None and precision == "fxp16":
        work += _vmem((h + 2 * p, w + 2 * p, cin_p), 4, layout)   # widened x
    return Footprint(
        vmem_bytes=bufs * blocks + work,
        hbm_bytes=n * tiles * (x_blk + w_blk) + n * h * w * cout_p * elt,
        flops=2 * n * h * w * k * k * cin_p * cout_p,
        mxu_util=_dot_util(h * w, k * k * cin_p, tco, mxu))


def conv2d_bwd_footprint(s: int, n: int, hg: int, wg: int, k: int, c: int,
                         cout: int, co_tile: int, *, pooled: bool,
                         gated: bool = True, precision: str = "f32",
                         mxu: int = 128,
                         layout: Optional[Tuple[int, int]] = None
                         ) -> Footprint:
    """One grid cell of the FUSED conv backward
    (:func:`conv2d_bwd_fused_pallas`): unpool + mask-gate prologues and the
    flipped-transpose single-dot BP in one call.

    ``s`` seeds share the cell (the seeds axis folds into the sublane dim);
    ``c`` is the contraction channel count (the forward Cout), ``cout`` the
    outgoing channels (the forward Cin).  ``hg/wg`` are the INCOMING
    gradient's spatial dims (post-pool when ``pooled``).
    """
    elt = _elt(precision)
    p = (k - 1) // 2
    cp = align_up(c, SUBLANE)
    tco, cout_p = cout_tiling(cout, co_tile)
    h, w = (2 * hg, 2 * wg) if pooled else (hg, wg)
    g_blk = s * hg * wg * cp * elt
    w_blk = k * k * cp * tco * elt
    idx_blk = hg * wg * cp // CRUMBS_PER_BYTE if pooled else 0
    mask_blk = h * w * cp // BITS_PER_BYTE if gated else 0
    tiles = cout_p // tco
    loads = g_blk + w_blk + idx_blk + mask_blk
    bufs = 1 if layout is None else 2
    blocks = (_vmem((s, hg, wg, cp), elt, layout)
              + _vmem((k * k, cp, tco), elt, layout)
              + _vmem((s, h, w, tco), elt, layout))
    if pooled:
        blocks += _vmem((hg, wg, cp // CRUMBS_PER_BYTE), 1, layout)
    if gated:
        blocks += _vmem((h, w, cp // BITS_PER_BYTE), 1, layout)
    # in-kernel values: the halo-padded gradient and the im2col dot; tiled
    # VMEM also holds the unpooled/gated gradient (fxp16: on the int32
    # carrier), the 32-bit unpool scratch, and the unpacked indices and
    # mask, whose [..., bytes, fields] int32 intermediates pad the short
    # fields axis to a whole lane tile
    val = 4 if layout is not None and precision == "fxp16" else elt
    work = (_vmem((s, h + 2 * p, w + 2 * p, cp), val, layout)
            + _im2col_work(s * h * w, k * k * cp, tco, precision, layout))
    if layout is not None:
        work += _vmem((s, h, w, cp), val, layout)
        if pooled:
            work += (_vmem((s, h, w, cp), 4, layout)
                     + _vmem((hg, wg, cp // CRUMBS_PER_BYTE, CRUMBS_PER_BYTE),
                             4, layout))
        if gated:
            work += _vmem((h, w, cp // BITS_PER_BYTE, BITS_PER_BYTE), 4,
                          layout)
    return Footprint(
        vmem_bytes=bufs * blocks + work,
        hbm_bytes=n * tiles * loads + s * n * h * w * cout_p * elt,
        flops=2 * s * n * h * w * k * k * cp * cout_p,
        mxu_util=_dot_util(s * h * w, k * k * cp, tco, mxu))


# ---------------------------------------------------------------------------
# vmm family (tiled FC matmul; repro.kernels.vmm)
# ---------------------------------------------------------------------------


def vmm_fwd_footprint(m: int, k: int, n: int, tm: int, tk: int, tn: int,
                      precision: str = "f32", mxu: int = 128) -> Footprint:
    """One (M, N, K-step) grid cell of :func:`vmm_pallas`: x/w blocks, the
    output-stationary accumulator scratch, and the output block."""
    elt, acc = _elt(precision), ACC_BYTES[precision]
    tm_, tk_, tn_, mp, kp, np_ = vmm_tiling(m, k, n, tm, tk, tn)
    x_blk = tm_ * tk_ * elt
    w_blk = tk_ * tn_ * elt
    acc_blk = tm_ * tn_ * acc
    out_blk = tm_ * tn_ * elt
    cells = (mp // tm_) * (np_ // tn_) * (kp // tk_)
    return Footprint(
        vmem_bytes=x_blk + w_blk + acc_blk + out_blk,
        hbm_bytes=cells * (x_blk + w_blk) + mp * np_ * elt,
        flops=2 * mp * kp * np_,
        mxu_util=_dot_util(tm_, tk_, tn_, mxu))


def vmm_bwd_footprint(s: int, m: int, k: int, n: int, tk: int, tn: int, *,
                      gated: bool = True, out_gated: bool = False,
                      precision: str = "f32", mxu: int = 128) -> Footprint:
    """One grid cell of the FUSED FC backward
    (:func:`vmm_bwd_fused_pallas`): the full sublane-padded M rows ride
    each cell (seeds on the grid), mask unpack + gating fused in."""
    elt, acc = _elt(precision), ACC_BYTES[precision]
    _, tk_, tn_, mp, kp, np_ = vmm_tiling(m, k, n, m, tk, tn)
    g_blk = mp * tk_ * elt
    w_blk = tk_ * tn_ * elt
    mask_blk = mp * tk_ // BITS_PER_BYTE if gated else 0
    omask_blk = mp * tn_ // BITS_PER_BYTE if out_gated else 0
    acc_blk = mp * tn_ * acc
    out_blk = mp * tn_ * elt
    cells = s * (np_ // tn_) * (kp // tk_)
    loads = g_blk + w_blk + mask_blk + omask_blk
    return Footprint(
        vmem_bytes=g_blk + w_blk + mask_blk + omask_blk + acc_blk + out_blk,
        hbm_bytes=cells * loads + s * mp * np_ * elt,
        flops=2 * s * mp * kp * np_,
        mxu_util=_dot_util(mp, tk_, tn_, mxu))


# ---------------------------------------------------------------------------
# pool family (no tile knobs — budget check only)
# ---------------------------------------------------------------------------


def pool_footprint(n: int, h: int, w: int, c: int,
                   precision: str = "f32") -> Footprint:
    """One batch cell of :func:`maxpool_fwd_pallas`: feature map in, pooled
    map + packed 2-bit indices out.  No tile knobs — reported so a plan's
    budget audit covers every kernel the layer stack launches."""
    elt = _elt(precision)
    cp = align_up(c, CRUMBS_PER_BYTE)
    x_blk = h * w * cp * elt
    y_blk = (h // 2) * (w // 2) * cp * elt
    idx_blk = (h // 2) * (w // 2) * cp // CRUMBS_PER_BYTE
    # the four strided window candidate views materialized for the select
    cand_blk = 4 * y_blk
    return Footprint(
        vmem_bytes=x_blk + cand_blk + y_blk + idx_blk,
        hbm_bytes=n * (x_blk + y_blk + idx_blk),
        flops=0,
        mxu_util=1.0)


# ---------------------------------------------------------------------------
# ssm_scan family (selective-scan recurrence; repro.kernels.ssm_scan)
# ---------------------------------------------------------------------------


def ssm_scan_footprint(b: int, s: int, d: int, n: int,
                       d_tile: int = None, chunk: int = None,
                       precision: str = "f32",
                       layout: Optional[Tuple[int, int]] = None) -> Footprint:
    """One (batch, d-tile, chunk) grid cell of :func:`selective_scan_pallas`.

    The scan is a VPU recurrence (no MXU dots), so like
    :func:`pool_footprint` it reports ``flops=0`` / full ``mxu_util`` and is
    ranked purely by memory traffic — smaller chunks reload the per-channel
    A matrix and the carried state more often, so the planner prefers the
    largest (chunk, d_tile) pair that fits the budget.

    Block accounting mirrors the kernel's BlockSpecs exactly: dt is cast to
    f32 at the call site (4 B regardless of ``precision``), x/y ride the
    operand dtype, the [chunk, n, 1] B/C column blocks, the [n, d_tile]
    A/h blocks and the state scratch are f32.  On tiled VMEM (``layout``)
    every block pads to whole tiles (a B/C column to a full lane row) and
    the pipelined in/out blocks are double-buffered.  ``d_tile=None``
    models the UNPLANNED launch (the whole ``d`` axis in one cell — what
    the attribution step runs without a plan); ``chunk=None`` defaults the
    chunk length to the full sequence.
    """
    elt = _elt(precision)
    dt_t = min(d_tile if d_tile is not None else d, d)
    ck = min(chunk if chunk is not None else s, s)
    n_chunks = -(-s // ck)
    dt_blk = _vmem((ck, dt_t), 4, layout)   # dt cast to f32 at the call site
    x_blk = _vmem((ck, dt_t), elt, layout)
    bc_blk = 2 * _vmem((ck, n, 1), 4, layout)     # B and C columns, f32
    a_blk = _vmem((n, dt_t), 4, layout)
    h0_blk = _vmem((n, dt_t), 4, layout)
    scr = _vmem((n, dt_t), 4, layout)       # carried-state VMEM scratch
    y_blk = _vmem((ck, dt_t), elt, layout)
    hl_blk = _vmem((n, dt_t), 4, layout)
    cells = b * (d // dt_t if d % dt_t == 0 else -(-d // dt_t)) * n_chunks
    loads = (ck * dt_t * (4 + elt) + 2 * ck * n * 4 + 2 * dt_t * n * 4)
    buffers = 2 if layout is not None else 1
    return Footprint(
        vmem_bytes=(buffers * (dt_blk + x_blk + bc_blk + a_blk + h0_blk
                               + y_blk + hl_blk) + scr),
        hbm_bytes=cells * loads + b * n_chunks * ck * d * elt + b * d * n * 4,
        flops=0,
        mxu_util=1.0)
