"""Jit'd selective-scan wrapper.

Forward runs the Pallas state-stationary kernel, which also keeps the
state entering each chunk; the backward is the scan's adjoint recurrence,
the Pallas kernel ``ssm_scan_bwd``: it recomputes one chunk's states from
the kept one and walks the chunk backwards, last chunk first.

``d_tile``/``chunk`` are the planner's knobs (``repro.plan.ScanTile``): how
many channels ride one grid cell and how many timesteps one sequential chunk
covers.  They split the grid, never the math — each (d, n) element's
per-timestep trajectory is computed in the same op order regardless of the
split, so planned and default launches are bitwise-identical.  The knobs are
launch parameters, not traced values, so each distinct pair gets its own
memoized ``custom_vjp`` wrapper (the bare positional call
``selective_scan(dt, x, B, C, a, h0)`` keeps the kernel defaults).
"""
from __future__ import annotations

import functools

import jax

from repro.kernels.ssm_scan.ssm_scan import (selective_scan_bwd_pallas,
                                             selective_scan_pallas)

_DEFAULT_D_TILE = 256
_DEFAULT_CHUNK = 64


@functools.lru_cache(maxsize=None)
def _knobbed(d_tile: int, chunk: int):
    @jax.custom_vjp
    def scan(dt, x, bmat, cmat, a, h0):
        return selective_scan_pallas(dt, x, bmat, cmat, a, h0,
                                     d_tile=d_tile, chunk=chunk)

    def _fwd(dt, x, bmat, cmat, a, h0):
        y, h_last, starts = selective_scan_pallas(
            dt, x, bmat, cmat, a, h0, d_tile=d_tile, chunk=chunk,
            with_starts=True)
        return (y, h_last), (dt, x, bmat, cmat, a, h0, starts)

    def _bwd(res, g):
        dt, x, bmat, cmat, a, h0, starts = res
        gy, gh = g
        grads = selective_scan_bwd_pallas(dt, x, bmat, cmat, a, starts, gy,
                                          gh, chunk=chunk, d_tile=d_tile)
        return tuple(gv.astype(v.dtype) for gv, v in
                     zip(grads, (dt, x, bmat, cmat, a, h0)))

    scan.defvjp(_fwd, _bwd)
    return scan


def selective_scan(dt, x, bmat, cmat, a, h0, *, d_tile=None, chunk=None):
    """(dt, x [B,S,D], B/C [B,S,N], A [D,N], h0 [B,D,N]) -> (y, h_last)."""
    return _knobbed(int(d_tile) if d_tile is not None else _DEFAULT_D_TILE,
                    int(chunk) if chunk is not None else _DEFAULT_CHUNK)(
        dt, x, bmat, cmat, a, h0)
