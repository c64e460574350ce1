"""Selective-scan (mamba-1) Pallas kernels — the SSM compute hot-spot.

TPU-native design (vs the CUDA warp-level kernel the paper family uses on
GPU): the recurrent state h [N, D_tile] — the state size on the sublanes,
the channels on the lanes, so a state of 16 x 512 is eight whole vregs —
is carried by the kernel across the sequence-chunk grid dimension in a
VMEM scratch (exactly like the output-stationary accumulator of the
paper's conv/VMM blocks — state stationary, inputs streamed HBM -> VMEM
chunk by chunk).  Within a chunk the recurrence runs as a ``fori_loop`` of
VPU element-wise ops on the state held in registers: dt, x and y are
[1, D_tile] rows of their [chunk, D_tile] blocks, B and C [N, 1] columns
(passed as [B, S, N, 1]); the output contraction <h, C_t> is fused in, so
the [B, S, D, N] discretized tensors never exist anywhere — the memory
property that makes SSM archs the long_500k family.

Grid: (batch, D tiles, S chunks)  —  S chunks is the ARBITRARY (sequential)
axis; the state carries across it.  The backward (``ssm_scan_bwd``) walks
the same grid with the chunks in reverse.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import compiler_params, interpret_mode
from jax.experimental.pallas import tpu as pltpu


def _scan_kernel(dt_ref, x_ref, b_ref, c_ref, a_ref, h0_ref,
                 y_ref, hout_ref, *rest, ck: int, n_chunks: int):
    starts_ref, h_scratch = rest if len(rest) == 2 else (None, rest[0])

    @pl.when(pl.program_id(2) == 0)
    def _init():
        h_scratch[...] = h0_ref[0]              # [N, Dt] f32

    if starts_ref is not None:                  # the state entering the chunk
        starts_ref[0, 0] = h_scratch[...]

    a = a_ref[...]                              # [N, Dt] (A = -exp(A_log))

    def step(t, h):
        dt_t = dt_ref[0, pl.ds(t, 1), :]        # [1, Dt]
        x_t = x_ref[0, pl.ds(t, 1), :]
        h = jnp.exp(dt_t * a) * h + b_ref[0, t] * (dt_t * x_t)
        y_ref[0, pl.ds(t, 1), :] = jnp.sum(
            h * c_ref[0, t], axis=0, keepdims=True).astype(y_ref.dtype)
        return h

    h_scratch[...] = jax.lax.fori_loop(0, ck, step, h_scratch[...])

    @pl.when(pl.program_id(2) == n_chunks - 1)
    def _flush():
        hout_ref[0] = h_scratch[...]


def _columns(v, pad: int):
    """[B, S, N] -> [B, S + pad, N, 1] float32: each step's N values as a
    column, zeros after ``S``."""
    v = jnp.pad(v.astype(jnp.float32), ((0, 0), (0, pad), (0, 0)))
    return v[..., None]


def selective_scan_pallas(dt, x, bmat, cmat, a, h0, *, d_tile: int = 256,
                          chunk: int = 64, interpret: Optional[bool] = None,
                          with_starts: bool = False):
    """dt/x [B,S,D] f32/bf16, bmat/cmat [B,S,N], a [D,N] f32, h0 [B,D,N] f32.

    Returns (y [B,S,D] (x.dtype), h_last [B,D,N] f32), and with
    ``with_starts`` also the state entering every chunk,
    [B, ceil(S / chunk), N, D] f32 (state-major): what the backward
    recomputes from.
    """
    interpret = interpret_mode(interpret)
    b, s, d = x.shape
    n = a.shape[1]
    dt_t = min(d_tile, d)
    assert d % dt_t == 0, (d, dt_t)
    ck = min(chunk, s)
    n_chunks = -(-s // ck)
    pad = n_chunks * ck - s
    rows = lambda v: jnp.pad(v, ((0, 0), (0, pad), (0, 0))) if pad else v

    row_spec = pl.BlockSpec((1, ck, dt_t), lambda i, j, c: (i, c, j))
    col_spec = pl.BlockSpec((1, ck, n, 1), lambda i, j, c: (i, c, 0, 0))
    state_spec = pl.BlockSpec((1, n, dt_t), lambda i, j, c: (i, 0, j))
    out_specs = [row_spec, state_spec]                          # y, h_last
    out_shape = [jax.ShapeDtypeStruct((b, n_chunks * ck, d), x.dtype),
                 jax.ShapeDtypeStruct((b, n, d), jnp.float32)]
    if with_starts:
        out_specs.append(pl.BlockSpec((1, 1, n, dt_t),
                                      lambda i, j, c: (i, c, 0, j)))
        out_shape.append(jax.ShapeDtypeStruct((b, n_chunks, n, d),
                                              jnp.float32))
    outs = pl.pallas_call(
        functools.partial(_scan_kernel, ck=ck, n_chunks=n_chunks),
        grid=(b, d // dt_t, n_chunks),
        in_specs=[
            row_spec, row_spec, col_spec, col_spec,                 # dt x B C
            pl.BlockSpec((n, dt_t), lambda i, j, c: (0, j)),        # A^T
            state_spec,                                             # h0
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((n, dt_t), jnp.float32)],
        name="ssm_scan",
        compiler_params=compiler_params(),
        interpret=interpret,
    )(rows(dt.astype(jnp.float32)), rows(x), _columns(bmat, pad),
      _columns(cmat, pad), a.T, jnp.swapaxes(h0, 1, 2))
    y, h_last = outs[0][:, :s], jnp.swapaxes(outs[1], 1, 2)
    return (y, h_last, outs[2]) if with_starts else (y, h_last)


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------


def _scan_bwd_kernel(dt_ref, x_ref, b_ref, c_ref, gy_ref, at_ref, start_ref,
                     gh_ref, gdt_ref, gx_ref, gb_ref, gc_ref, ga_ref,
                     gh0_ref, hs, mu, ga, *, ck: int, n_chunks: int):
    """One (batch, d-tile, chunk) cell, chunks walked last to first, in the
    forward's layout.  ``hs[t + 1]`` holds h_t of the chunk, recomputed from
    the state that entered it (``hs[0]``); ``mu`` carries the cotangent of
    the state leaving the step being walked."""
    @pl.when(pl.program_id(2) == 0)
    def _init():
        mu[...] = gh_ref[0]
        ga[...] = jnp.zeros_like(ga)

    a = at_ref[...]                                       # [N, Dt]
    hs[0] = start_ref[0, 0]

    def forward(t, h):
        dt_t = dt_ref[0, pl.ds(t, 1), :]                  # [1, Dt]
        w_t = dt_t * x_ref[0, pl.ds(t, 1), :]
        h = jnp.exp(dt_t * a) * h + b_ref[0, t] * w_t     # [N, Dt]
        hs[t + 1] = h
        return h

    jax.lax.fori_loop(0, ck, forward, hs[0])

    def reverse(i, lam_next):
        t = ck - 1 - i
        dt_t = dt_ref[0, pl.ds(t, 1), :]
        x_t = x_ref[0, pl.ds(t, 1), :]
        gy_t = gy_ref[0, pl.ds(t, 1), :]
        b_t, c_t = b_ref[0, t], c_ref[0, t]               # [N, 1]
        w_t = dt_t * x_t
        abar = jnp.exp(dt_t * a)
        lam = lam_next + c_t * gy_t                       # dL/dh_t
        gc_ref[0, 0, t] = jnp.sum(hs[t + 1] * gy_t, axis=1, keepdims=True)
        gb_ref[0, 0, t] = jnp.sum(lam * w_t, axis=1, keepdims=True)
        gw = jnp.sum(lam * b_t, axis=0, keepdims=True)    # [1, Dt]
        gz = lam * hs[t] * abar                           # dL/d(dt_t A)
        gdt_ref[0, pl.ds(t, 1), :] = (jnp.sum(gz * a, axis=0, keepdims=True)
                                      + gw * x_t)
        gx_ref[0, pl.ds(t, 1), :] = gw * dt_t
        ga[...] += gz * dt_t
        return abar * lam

    mu[...] = jax.lax.fori_loop(0, ck, reverse, mu[...])

    @pl.when(pl.program_id(2) == n_chunks - 1)
    def _flush():
        gh0_ref[0] = mu[...]
        ga_ref[0] = ga[...]


def selective_scan_bwd_pallas(dt, x, bmat, cmat, a, starts, gy, gh, *,
                              chunk: int, d_tile: int = 256,
                              interpret: Optional[bool] = None):
    """The scan's adjoint, as a Pallas kernel named ``ssm_scan_bwd``.

    With ``lam_t = dL/dh_t`` (the cotangent of ``h_t`` through ``y_t`` and
    every later state), walked from the last step to the first:

        lam_t          = C_t gy_t + exp(dt_{t+1} A) lam_{t+1},  lam_S += gh
        dL/dC_t        = sum_d h_t gy_t
        dL/dB_t        = sum_d lam_t dt_t x_t
        dL/d(dt_t x_t) = sum_n lam_t B_t
        dL/d(dt_t A)   = lam_t h_{t-1} exp(dt_t A)
        dL/dh_0        = exp(dt_1 A) lam_1

    ``starts`` is :func:`selective_scan_pallas`'s ``with_starts`` output
    for the same ``chunk``; each cell recomputes its chunk's states from
    it in VMEM, then walks the chunk backwards.  Arguments as the forward,
    plus ``gy`` [B, S, D] and ``gh`` [B, D, N]; returns the cotangents of
    (dt, x, bmat, cmat, a, h0) in float32.
    """
    interpret = interpret_mode(interpret)
    f32 = jnp.float32
    b, s, d = x.shape
    n = a.shape[1]
    n_chunks = starts.shape[1]
    ck = min(chunk, s)
    dt_t = min(d_tile, d)
    assert d % dt_t == 0, (d, dt_t)
    n_dt = d // dt_t
    pad = n_chunks * ck - s
    rows = lambda v: jnp.pad(v.astype(f32), ((0, 0), (0, pad), (0, 0)))
    last = n_chunks - 1
    row_spec = pl.BlockSpec((1, ck, dt_t), lambda i, j, c: (i, last - c, j))
    col_spec = pl.BlockSpec((1, ck, n, 1),
                            lambda i, j, c: (i, last - c, 0, 0))
    state_spec = pl.BlockSpec((1, n, dt_t), lambda i, j, c: (i, 0, j))
    part_spec = pl.BlockSpec((1, 1, ck, n, 1),
                             lambda i, j, c: (i, j, last - c, 0, 0))
    gdt, gx, gb, gc, ga, gh0 = pl.pallas_call(
        functools.partial(_scan_bwd_kernel, ck=ck, n_chunks=n_chunks),
        grid=(b, n_dt, n_chunks),
        in_specs=[
            row_spec, row_spec, col_spec, col_spec, row_spec,       # dt x B C gy
            pl.BlockSpec((n, dt_t), lambda i, j, c: (0, j)),        # A^T
            pl.BlockSpec((1, 1, n, dt_t),
                         lambda i, j, c: (i, last - c, 0, j)),      # starts
            state_spec,                                             # gh
        ],
        out_specs=[row_spec, row_spec, part_spec, part_spec, state_spec,
                   state_spec],
        out_shape=[
            jax.ShapeDtypeStruct((b, n_chunks * ck, d), f32),       # d dt
            jax.ShapeDtypeStruct((b, n_chunks * ck, d), f32),       # d x
            jax.ShapeDtypeStruct((b, n_dt, n_chunks * ck, n, 1), f32),
            jax.ShapeDtypeStruct((b, n_dt, n_chunks * ck, n, 1), f32),
            jax.ShapeDtypeStruct((b, n, d), f32),                   # d A^T
            jax.ShapeDtypeStruct((b, n, d), f32),                   # d h0^T
        ],
        scratch_shapes=[pltpu.VMEM((ck + 1, n, dt_t), f32),
                        pltpu.VMEM((n, dt_t), f32),
                        pltpu.VMEM((n, dt_t), f32)],
        name="ssm_scan_bwd",
        compiler_params=compiler_params(),
        interpret=interpret,
    )(rows(dt), rows(x), _columns(bmat, pad), _columns(cmat, pad), rows(gy),
      a.T.astype(f32), starts, jnp.swapaxes(gh.astype(f32), 1, 2))
    return (gdt[:, :s], gx[:, :s], gb.sum(axis=1)[:, :s, :, 0],
            gc.sum(axis=1)[:, :s, :, 0], ga.sum(axis=0).T,
            jnp.swapaxes(gh0, 1, 2))
