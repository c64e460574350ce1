"""Mamba-1 selective-scan block (falcon-mamba; SSM branch of hymba).

TPU adaptation of the CUDA selective-scan: the recurrence
``h_t = Abar_t * h_{t-1} + Bbar_t x_t`` (diagonal A) is evaluated as a
*chunked parallel scan* — ``lax.associative_scan`` inside fixed-size chunks
(VMEM-friendly: the [B, chunk, d_inner, N] discretized tensors never
materialize for the full sequence, the classic mamba memory blow-up), with
the inter-chunk state carried by ``lax.scan``.  Decode is the O(1) recurrent
update with a rolled conv window, which is what makes the long_500k cell
feasible for the SSM archs (DESIGN.md §4).

All gates go through rules.act so attribution BP crosses the SSM with the
configured method/residual policy.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.core import rules
from repro.dist.sharding import constrain
from repro.models import layers


def init_mamba(key, cfg):
    d, di, n, dtr = cfg.d_model, cfg.d_inner, cfg.ssm_state, cfg.dtr
    ks = jax.random.split(key, 6)
    # S4-style A init: -[1..N] per channel
    a = jnp.tile(jnp.arange(1, n + 1, dtype=jnp.float32)[None, :], (di, 1))
    p = {
        "in_proj": layers.dense_init(ks[0], d, 2 * di, cfg.jdtype),
        "conv_w": (jax.random.normal(ks[1], (cfg.ssm_conv, di), jnp.float32)
                   * (1.0 / cfg.ssm_conv)).astype(cfg.jdtype),
        "conv_b": jnp.zeros((di,), cfg.jdtype),
        "x_proj": layers.dense_init(ks[2], di, dtr + 2 * n, cfg.jdtype),
        "dt_proj": layers.dense_init(ks[3], dtr, di, cfg.jdtype),
        "dt_bias": jnp.full((di,), -4.6, jnp.float32),   # softplus ~= 0.01
        "A_log": jnp.log(a),
        "D": jnp.ones((di,), jnp.float32),
        "out_proj": layers.dense_init(ks[4], di, d, cfg.jdtype),
    }
    if cfg.ssm_inner_norm:
        p["dt_norm"] = layers.norm_init(dtr, "rmsnorm")
        p["b_norm"] = layers.norm_init(n, "rmsnorm")
        p["c_norm"] = layers.norm_init(n, "rmsnorm")
    return p


def _causal_conv(x, w, b, state: Optional[jnp.ndarray] = None):
    """Depthwise causal conv1d, kernel k (small, unrolled taps).

    x: [B, S, di]; w: [k, di].  With ``state`` [B, k-1, di] (decode), the
    window is state||x.  Returns (y, new_state).
    """
    k = w.shape[0]
    if state is None:
        pad = jnp.zeros((x.shape[0], k - 1, x.shape[2]), x.dtype)
    else:
        pad = state.astype(x.dtype)
    xp = jnp.concatenate([pad, x], axis=1)           # [B, S+k-1, di]
    y = sum(xp[:, i:i + x.shape[1]] * w[i] for i in range(k)) + b
    new_state = xp[:, -(k - 1):]
    return y, new_state


def _chunk_scan(abar, bx, h0):
    """One chunk: h_t = abar_t * h_{t-1} + bx_t, h_0 seeded by carry h0.

    abar, bx: [B, C, di, N] (f32); h0: [B, di, N].
    Returns (h_all [B, C, di, N], h_last).
    """
    def combine(l, r):
        al, bl = l
        ar, br = r
        return al * ar, ar * bl + br

    a_cum, b_cum = jax.lax.associative_scan(combine, (abar, bx), axis=1)
    h_all = a_cum * h0[:, None] + b_cum
    return h_all, h_all[:, -1]


def mamba_core(p, x, cfg, method="autodiff",
               state: Optional[dict] = None, pos=None,
               use_pallas: bool = False, scan_tile=None, live=None):
    """x: [B, S, d] -> (out [B, S, d], new_state|None).

    state = {"h": [B, di, N] f32, "conv": [B, k-1, di]} for decode.
    ``use_pallas`` routes the full-sequence scan through the
    state-stationary Pallas kernel (kernels/ssm_scan) — the TPU serving
    hot path; its backward falls back to the sequential reference, so the
    training path keeps the chunked XLA scan.  ``scan_tile`` is a planned
    ``(d_tile, chunk)`` pair for that kernel's launch grid (implies the
    Pallas path; grid splits are bitwise-neutral for the scan, so a planned
    launch computes the same bits as the default one); ``use_pallas=True``
    alone keeps the kernel's default knobs.

    ``live`` [B, S] (bool) is False at the positions of left padding: the
    conv and the scan see zeros there, so the state reaching the first
    live position is the state of an unpadded sequence.
    """
    b, s, d = x.shape
    di, n = cfg.d_inner, cfg.ssm_state

    xz = x @ p["in_proj"]
    xz = constrain(xz, "batch", None, "model")
    xin, z = jnp.split(xz, 2, axis=-1)
    keep = None if live is None else live[..., None].astype(xin.dtype)
    if keep is not None:
        xin = xin * keep

    conv_state = state["conv"] if state is not None else None
    xc, new_conv = _causal_conv(xin, p["conv_w"], p["conv_b"], conv_state)
    xc = rules.act(xc, "silu", method, cfg.residual_policy)
    if keep is not None:
        xc = xc * keep

    bcdt = xc @ p["x_proj"]                               # [B, S, dtr+2N]
    dt_r, bmat, cmat = jnp.split(bcdt, [cfg.dtr, cfg.dtr + n], axis=-1)
    if cfg.ssm_inner_norm:                                # jamba
        dt_r = layers.apply_norm(p["dt_norm"], dt_r, "rmsnorm")
        bmat = layers.apply_norm(p["b_norm"], bmat, "rmsnorm")
        cmat = layers.apply_norm(p["c_norm"], cmat, "rmsnorm")
    dt = jax.nn.softplus((dt_r @ p["dt_proj"]).astype(jnp.float32)
                         + p["dt_bias"])                  # [B, S, di] f32
    a = -jnp.exp(p["A_log"])                              # [di, N]

    h_init = (state["h"] if state is not None
              else jnp.zeros((b, di, n), jnp.float32))

    if s == 1:                                            # decode: O(1) update
        abar = jnp.exp(dt[..., None] * a)
        bx = (dt[..., None] * bmat.astype(jnp.float32)[:, :, None, :]
              * xc.astype(jnp.float32)[..., None])
        h_new = abar[:, 0] * h_init + bx[:, 0]
        h_last = h_new
        y = jnp.einsum("bdn,bn->bd", h_new,
                       cmat[:, 0].astype(jnp.float32))[:, None].astype(x.dtype)
    elif use_pallas or scan_tile is not None:
        from repro.kernels.ssm_scan import ops as scan_ops
        d_tile, chunk = scan_tile if scan_tile is not None else (None, None)
        y, h_last = scan_ops.selective_scan(
            dt.astype(jnp.float32), xc, bmat, cmat, a, h_init,
            d_tile=d_tile, chunk=chunk)
        y = y.astype(x.dtype)
    else:
        # Chunked selective scan with the discretization (abar, bx) AND the
        # output contraction C.h computed INSIDE the chunk body: the
        # [B, S, d_inner, N] tensors never materialize beyond one chunk —
        # the mamba-kernel memory fix (132 GB -> per-chunk MBs of temps on
        # hymba train; see EXPERIMENTS.md §Perf).
        ck = min(cfg.ssm_chunk, s)
        nchunks = -(-s // ck)
        pad = nchunks * ck - s

        def chunkify(v, fill=0.0):
            if pad:
                cfgp = [(0, 0)] * v.ndim
                cfgp[1] = (0, pad)
                v = jnp.pad(v, cfgp, constant_values=fill)
            return v.reshape((b, nchunks, ck) + v.shape[2:]).swapaxes(0, 1)

        dt_c = chunkify(dt)                               # [nc, B, ck, di]
        bm_c = chunkify(bmat.astype(jnp.float32))         # [nc, B, ck, N]
        cm_c = chunkify(cmat.astype(jnp.float32))         # [nc, B, ck, N]
        xc_c = chunkify(xc.astype(jnp.float32))           # [nc, B, ck, di]

        def body(h, inputs):
            dtc, bmc, cmc, xcc = inputs
            abar = jnp.exp(dtc[..., None] * a)            # [B, ck, di, N]
            bx = dtc[..., None] * bmc[:, :, None, :] * xcc[..., None]
            h_all, h_last = _chunk_scan(abar, bx, h)
            yc = jnp.einsum("bcdn,bcn->bcd", h_all, cmc)  # fused C.h
            return h_last, yc

        h_last, y_c = jax.lax.scan(body, h_init, (dt_c, bm_c, cm_c, xc_c))
        y = (y_c.swapaxes(0, 1).reshape(b, nchunks * ck, di)[:, :s]
             .astype(x.dtype))

    y = y + xc * p["D"].astype(x.dtype)
    y = y * rules.act(z, "silu", method, cfg.residual_policy)
    y = constrain(y, "batch", None, "model")
    out = y @ p["out_proj"]
    out = constrain(out, "batch", None, None)

    new_state = None
    if state is not None:
        new_state = {"h": h_last, "conv": new_conv.astype(state["conv"].dtype)}
    return out, new_state


def init_state(cfg, batch: int, dtype=None):
    """Decode state for one mamba block."""
    return {
        "h": jnp.zeros((batch, cfg.d_inner, cfg.ssm_state), jnp.float32),
        "conv": jnp.zeros((batch, cfg.ssm_conv - 1, cfg.d_inner),
                          dtype or cfg.jdtype),
    }
