"""Selective-scan (mamba-1) Pallas kernel — the SSM compute hot-spot.

TPU-native design (vs the CUDA warp-level kernel the paper family uses on
GPU): the recurrent state h [D_tile, N] lives in a VMEM scratch that
PERSISTS across the sequence-chunk grid dimension (exactly like the
output-stationary accumulator of the paper's conv/VMM blocks — state
stationary, inputs streamed HBM -> VMEM chunk by chunk).  Within a chunk
the recurrence runs as a ``fori_loop`` of VPU element-wise ops on
[D_tile, N] registers; the output contraction <h, C_t> is fused in, so the
[B, S, D, N] discretized tensors never exist anywhere — the memory
property that makes SSM archs the long_500k family.

Grid: (batch, D tiles, S chunks)  —  S chunks is the ARBITRARY (sequential)
axis; h_scratch carries across it.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import interpret_mode
from jax.experimental.pallas import tpu as pltpu


def _scan_kernel(dt_ref, x_ref, b_ref, c_ref, a_ref, h0_ref,
                 y_ref, hout_ref, h_scratch, *, ck: int, n_chunks: int):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        h_scratch[...] = h0_ref[0]              # [Dt, N] f32

    a = a_ref[...]                              # [Dt, N] (A = -exp(A_log))

    def step(t, _):
        dt_t = dt_ref[0, t, :]                  # [Dt]
        x_t = x_ref[0, t, :]                    # [Dt]
        b_t = b_ref[0, t, :]                    # [N]
        c_t = c_ref[0, t, :]                    # [N]
        abar = jnp.exp(dt_t[:, None] * a)       # [Dt, N]
        bx = (dt_t * x_t)[:, None] * b_t[None, :]
        h = abar * h_scratch[...] + bx
        h_scratch[...] = h
        y_ref[0, t, :] = jnp.sum(h * c_t[None, :], axis=1).astype(y_ref.dtype)
        return ()

    jax.lax.fori_loop(0, ck, step, ())

    @pl.when(pl.program_id(2) == n_chunks - 1)
    def _flush():
        hout_ref[0] = h_scratch[...]


def selective_scan_pallas(dt, x, bmat, cmat, a, h0, *, d_tile: int = 256,
                          chunk: int = 64, interpret: Optional[bool] = None):
    """dt/x [B,S,D] f32/bf16, bmat/cmat [B,S,N], a [D,N] f32, h0 [B,D,N] f32.

    Returns (y [B,S,D] (x.dtype), h_last [B,D,N] f32).
    """
    interpret = interpret_mode(interpret)
    b, s, d = x.shape
    n = a.shape[1]
    dt_t = min(d_tile, d)
    assert d % dt_t == 0, (d, dt_t)
    ck = min(chunk, s)
    n_chunks = -(-s // ck)
    pad = n_chunks * ck - s
    if pad:
        zpad = lambda v: jnp.pad(v, ((0, 0), (0, pad)) + ((0, 0),) * (v.ndim - 2))
        dt, x, bmat, cmat = map(zpad, (dt, x, bmat, cmat))

    grid = (b, d // dt_t, n_chunks)
    y, h_last = pl.pallas_call(
        functools.partial(_scan_kernel, ck=ck, n_chunks=n_chunks),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, ck, dt_t), lambda i, j, c: (i, c, j)),   # dt
            pl.BlockSpec((1, ck, dt_t), lambda i, j, c: (i, c, j)),   # x
            pl.BlockSpec((1, ck, n), lambda i, j, c: (i, c, 0)),      # B
            pl.BlockSpec((1, ck, n), lambda i, j, c: (i, c, 0)),      # C
            pl.BlockSpec((dt_t, n), lambda i, j, c: (j, 0)),          # A
            pl.BlockSpec((1, dt_t, n), lambda i, j, c: (i, j, 0)),    # h0
        ],
        out_specs=[
            pl.BlockSpec((1, ck, dt_t), lambda i, j, c: (i, c, j)),   # y
            pl.BlockSpec((1, dt_t, n), lambda i, j, c: (i, j, 0)),    # h_last
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, n_chunks * ck, d), x.dtype),
            jax.ShapeDtypeStruct((b, d, n), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((dt_t, n), jnp.float32)],
        name="ssm_scan",
        interpret=interpret,
    )(dt.astype(jnp.float32), x, bmat.astype(jnp.float32),
      cmat.astype(jnp.float32), a, h0)
    return y[:, :s], h_last
