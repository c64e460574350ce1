"""Shared building blocks for the assigned LM-family backbones.

Everything is functional JAX (param pytrees + pure apply fns) so the same
code serves CPU smoke tests, the 512-chip dry-run (via logical-axis
constraints from :mod:`repro.dist.sharding`) and attribution (every
nonlinearity routes through :mod:`repro.core.rules`, so the paper's
method-switch reaches every backbone).

Attention supports three execution shapes:
  * full       — materialized scores; short sequences.
  * chunked    — flash-style online-softmax double-chunking (q outer python
                 loop, kv inner ``lax.scan``); bounded memory for 32k prefill.
                 ``triangle_skip`` statically skips fully-masked kv chunks of
                 causal attention (hillclimb optimization, default on).
  * decode     — one query token against a fused-layout KV cache.

KV caches are stored FUSED as [B, T, Kv*hd] so the head axis never needs an
uneven GSPMD sharding (kv-heads x head_dim is 16-divisible for every
assigned arch; see DESIGN.md §7).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from jax.sharding import PartitionSpec as P

from repro.core import rules
from repro.dist.sharding import constrain, current_mesh

# ---------------------------------------------------------------------------
# initializers / norms
# ---------------------------------------------------------------------------


def dense_init(key, d_in: int, d_out: int, dtype, scale: Optional[float] = None):
    s = scale if scale is not None else (2.0 / (d_in + d_out)) ** 0.5
    return (jax.random.normal(key, (d_in, d_out), jnp.float32) * s).astype(dtype)


def norm_init(d: int, kind: str):
    if kind == "layernorm":
        return {"w": jnp.ones((d,), jnp.float32), "b": jnp.zeros((d,), jnp.float32)}
    return {"w": jnp.ones((d,), jnp.float32)}


def apply_norm(p, x, kind: str, eps: float = 1e-6):
    xf = x.astype(jnp.float32)
    if kind == "layernorm":
        mu = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.var(xf, axis=-1, keepdims=True)
        y = (xf - mu) * jax.lax.rsqrt(var + eps) * p["w"] + p["b"]
    else:
        ms = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
        y = xf * jax.lax.rsqrt(ms + eps) * p["w"]
    return y.astype(x.dtype)


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------


def rope_tables(positions: jnp.ndarray, head_dim: int, theta: float):
    """positions [S] -> (cos, sin) each [S, head_dim/2], f32."""
    inv = 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))
    ang = positions.astype(jnp.float32)[:, None] * inv[None, :]
    return jnp.cos(ang), jnp.sin(ang)


def apply_rope(x: jnp.ndarray, cos: jnp.ndarray, sin: jnp.ndarray):
    """x [B, S, H, D] with (cos, sin) [S, D/2]."""
    d2 = x.shape[-1] // 2
    x1, x2 = x[..., :d2], x[..., d2:]
    c = cos[None, :, None, :].astype(x.dtype)
    s = sin[None, :, None, :].astype(x.dtype)
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


# ---------------------------------------------------------------------------
# attention
# ---------------------------------------------------------------------------


def init_attention(key, cfg, d_model: Optional[int] = None):
    d = d_model or cfg.d_model
    hd, hq, kv = cfg.hd, cfg.n_heads, cfg.n_kv
    ks = jax.random.split(key, 4)
    p = {
        "wq": dense_init(ks[0], d, hq * hd, cfg.jdtype),
        "wk": dense_init(ks[1], d, kv * hd, cfg.jdtype),
        "wv": dense_init(ks[2], d, kv * hd, cfg.jdtype),
        "wo": dense_init(ks[3], hq * hd, d, cfg.jdtype),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((hq * hd,), cfg.jdtype)
        p["bk"] = jnp.zeros((kv * hd,), cfg.jdtype)
        p["bv"] = jnp.zeros((kv * hd,), cfg.jdtype)
    return p


def _split_heads(x, n, hd):
    return x.reshape(x.shape[:-1] + (n, hd))


def _sdpa_grouped(q, k, v, *, q_pos, k_pos, causal: bool, window: int):
    """Grouped-GQA sdpa for DECODE: q [B,1,Kv,G,hd] vs the UN-repeated cache
    k/v [B,T,Kv,hd].  Repeating kv (the full-seq head layout) would read Gx
    the KV cache per token — measured 9x collective regression on
    qwen2 decode_32k — while the grouped contraction touches each cache
    byte once."""
    scale = q.shape[-1] ** -0.5
    s = jnp.einsum("bskgh,btkh->bkgst", _grad_cast(q), _grad_cast(k),
                   preferred_element_type=jnp.float32) * scale
    mask = jnp.ones((q.shape[1], k.shape[1]), jnp.bool_)
    if causal:
        mask &= k_pos[None, :] <= q_pos[:, None]
    if window > 0:
        mask &= k_pos[None, :] > q_pos[:, None] - window
    s = jnp.where(mask[None, None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgst,btkh->bskgh", p.astype(v.dtype), _grad_cast(v),
                   preferred_element_type=jnp.float32)
    return o.astype(v.dtype)


def _head_layout(q, k4, v4, g: int):
    """Repeat kv heads to the q-head count and pin the head axis to "model".

    GQA with kv_heads < TP otherwise makes GSPMD split head_dim and emit
    partial-sum all-reduces inside every attention einsum; replicating kv
    across the query groups makes both sdpa einsums collective-free (head
    counts that don't divide 16 are padded internally by GSPMD — e.g.
    scout's 40 heads cost 48/40 = 20% head padding, vs ~4 s of ARs).
    """
    if g > 1:
        k4 = jnp.repeat(k4, g, axis=2)
        v4 = jnp.repeat(v4, g, axis=2)
    q = constrain(q, "batch", None, "model", None)
    k4 = constrain(k4, "batch", None, "model", None)
    v4 = constrain(v4, "batch", None, "model", None)
    return q, k4, v4


def _sdpa_full(q, k, v, *, q_pos, k_pos, causal: bool, window: int,
               k_live=None):
    """q [B,S,N,hd], k/v [B,T,N,hd] (kv already repeated to N heads);
    ``k_live`` [B,T] (bool) masks the keys of padded positions.

    Head-sharded: N lives on the "model" axis, so neither einsum contracts a
    sharded dim — zero attention collectives. (The previous grouped form let
    GSPMD split head_dim for kv-heads < TP, emitting thousands of partial-sum
    all-reduces: 42 MB x 4608 on scout train.)
    """
    scale = q.shape[-1] ** -0.5
    s = jnp.einsum("bsnh,btnh->bnst", _grad_cast(q), _grad_cast(k),
                   preferred_element_type=jnp.float32) * scale
    mask = jnp.ones((q.shape[1], k.shape[1]), jnp.bool_)
    if causal:
        mask &= k_pos[None, :] <= q_pos[:, None]
    if window > 0:
        mask &= k_pos[None, :] > q_pos[:, None] - window
    mask = mask[None, None]
    if k_live is not None:
        mask = mask & k_live[:, None, None, :]
    s = jnp.where(mask, s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bnst,btnh->bsnh", p.astype(v.dtype), _grad_cast(v),
                   preferred_element_type=jnp.float32)
    return o.astype(v.dtype)


def _sdpa_chunked(q, k, v, *, q_pos, k_pos, causal: bool, window: int,
                  qc: int, kc: int, triangle_skip: bool, k_live=None):
    """Flash-style double-chunked attention, online softmax, f32 running stats.

    q [B,S,N,hd], k/v [B,T,N,hd] (kv repeated to N heads — head-sharded, see
    _sdpa_full).  Outer loop over query chunks is a *python* loop (static),
    so with ``triangle_skip`` each causal q-chunk only ever sees kv chunks
    that can contain unmasked keys — a true (static) FLOPs reduction, not
    just masking; with a sliding window only the static BAND is computed.
    """
    b, sq, nh, hd = q.shape
    t = k.shape[1]
    nq = -(-sq // qc)
    scale = hd ** -0.5
    outs = []
    for i in range(nq):
        q0, q1 = i * qc, min((i + 1) * qc, sq)
        qb = q[:, q0:q1]
        qp = q_pos[q0:q1]
        t_lo = 0
        if triangle_skip and causal and t == sq:
            # Chunked attention is only used for full-sequence passes where
            # q_pos == k_pos == arange(S): keys beyond this q-chunk's last
            # position are fully masked, so skip those kv chunks STATICALLY
            # (a real FLOPs reduction — roughly 2x for long causal prefill).
            t_hi = min(t, (i + 1) * qc)
            if window > 0:
                # sliding window: keys before q0 - window are fully masked —
                # only the static BAND of kv chunks is ever computed
                # (~(window/S)x the full-block work for long SWA prefill).
                t_lo = max(0, (q0 - window) // kc * kc)
        else:
            t_hi = t
        t_hi = max(t_lo + kc, t_hi)
        nk = -(-(t_hi - t_lo) // kc)
        kk = k[:, t_lo: t_lo + nk * kc] if t_lo + nk * kc <= t else k[:, t_lo:]
        vv = v[:, t_lo: t_lo + nk * kc] if t_lo + nk * kc <= t else v[:, t_lo:]
        kpos_band = k_pos[t_lo: t_lo + kk.shape[1]]
        live_band = (k_live[:, t_lo: t_lo + kk.shape[1]]
                     if k_live is not None else None)

        def body(carry, j):
            m, l, acc = carry
            k_c = jax.lax.dynamic_slice_in_dim(kk, j * kc, kc, axis=1)
            v_c = jax.lax.dynamic_slice_in_dim(vv, j * kc, kc, axis=1)
            kp = jax.lax.dynamic_slice_in_dim(kpos_band, j * kc, kc, axis=0)
            s = jnp.einsum("bqnh,btnh->bnqt", _grad_cast(qb), _grad_cast(k_c),
                           preferred_element_type=jnp.float32) * scale
            msk = jnp.ones((qb.shape[1], kc), jnp.bool_)
            if causal:
                msk &= kp[None, :] <= qp[:, None]
            if window > 0:
                msk &= kp[None, :] > qp[:, None] - window
            msk = msk[None, None]
            if live_band is not None:
                msk = msk & jax.lax.dynamic_slice_in_dim(
                    live_band, j * kc, kc, axis=1)[:, None, None, :]
            s = jnp.where(msk, s, -1e30)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            corr = jnp.exp(m - m_new)
            e = jnp.exp(s - m_new[..., None])
            l_new = l * corr + jnp.sum(e, axis=-1)
            acc_new = acc * corr[..., None] + jnp.einsum(
                "bnqt,btnh->bnqh", e, v_c.astype(jnp.float32),
                preferred_element_type=jnp.float32)
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((b, nh, qb.shape[1]), -jnp.inf, jnp.float32)
        l0 = jnp.zeros((b, nh, qb.shape[1]), jnp.float32)
        a0 = jnp.zeros((b, nh, qb.shape[1], hd), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(body, (m0, l0, a0), jnp.arange(nk))
        o = acc / jnp.maximum(l, 1e-30)[..., None]
        outs.append(jnp.einsum("bnqh->bqnh", o).astype(v.dtype))
    return jnp.concatenate(outs, axis=1)


def attention(p, x, cfg, *, rope_cs=None, causal=True, window=0,
              cache=None, pos=None, kv_override=None, method="autodiff",
              chunked=None, triangle_skip=True, k_live=None):
    """GQA attention, all modes.

    cache: optional dict {"k","v": [B, Tcap, Kv*hd]} (fused layout).  With
    ``pos`` (scalar) given, runs single-token decode and returns updated cache.
    kv_override: (k4, v4) from a cross-attention source.
    k_live: optional [B, T] bool, False at the keys of padded positions
    (full-sequence passes only): no query attends to them.
    """
    b, s, _ = x.shape
    hd, hq, kvh = cfg.hd, cfg.n_heads, cfg.n_kv
    g = hq // kvh

    q2 = x @ p["wq"]
    if "bq" in p:
        q2 = q2 + p["bq"]
    q2 = constrain(q2, "batch", None, "model")
    q = _split_heads(q2, hq, hd)

    if kv_override is None:
        k2 = x @ p["wk"]
        v2 = x @ p["wv"]
        if "bk" in p:
            k2, v2 = k2 + p["bk"], v2 + p["bv"]
        k2 = constrain(k2, "batch", None, "model")
        v2 = constrain(v2, "batch", None, "model")
        k4 = _split_heads(k2, kvh, hd)
        v4 = _split_heads(v2, kvh, hd)
    else:
        k4, v4 = kv_override

    new_cache = cache
    if cache is not None and pos is not None:
        # ---- decode: write this step's fused kv at pos, read full cache ----
        q_pos = pos + jnp.arange(s)
        if rope_cs is not None:
            cq, sq_ = rope_tables(q_pos, hd, cfg.rope_theta)
            q = apply_rope(q, cq, sq_)
            if kv_override is None:
                k4 = apply_rope(k4, cq, sq_)   # cache stores rotated keys
        if kv_override is None:
            kf = k4.reshape(b, s, kvh * hd)
            vf = v4.reshape(b, s, kvh * hd)
            ck = jax.lax.dynamic_update_slice_in_dim(cache["k"], kf.astype(cache["k"].dtype), pos, axis=1)
            cv = jax.lax.dynamic_update_slice_in_dim(cache["v"], vf.astype(cache["v"].dtype), pos, axis=1)
            new_cache = {"k": ck, "v": cv}
        else:
            ck, cv = cache["k"], cache["v"]
        tcap = ck.shape[1]
        k4 = ck.reshape(b, tcap, kvh, hd)
        v4 = cv.reshape(b, tcap, kvh, hd)
        k_pos = jnp.arange(tcap)
        qg = q.reshape(b, s, kvh, g, hd)
        o = _sdpa_grouped(qg, k4, v4, q_pos=q_pos, k_pos=k_pos,
                          causal=causal, window=window)
    else:
        # ---- full-sequence (train / prefill) ----
        if rope_cs is not None:
            cos, sin = rope_cs
            q = apply_rope(q, cos, sin)
            if kv_override is None:
                k4 = apply_rope(k4, cos, sin)
        if cache is not None:   # prefill fills the cache
            kf = k4.reshape(b, s, kvh * hd).astype(cache["k"].dtype)
            vf = v4.reshape(b, s, kvh * hd).astype(cache["v"].dtype)
            ck = jax.lax.dynamic_update_slice_in_dim(cache["k"], kf, 0, axis=1)
            cv = jax.lax.dynamic_update_slice_in_dim(cache["v"], vf, 0, axis=1)
            new_cache = {"k": ck, "v": cv}
        t = k4.shape[1]
        q_pos = jnp.arange(s)
        k_pos = jnp.arange(t)
        qh, kh, vh = _head_layout(q, k4, v4, g)
        use_chunked = chunked if chunked is not None else s >= cfg.attn_chunk_threshold
        if use_chunked:
            o = _sdpa_chunked(qh, kh, vh, q_pos=q_pos, k_pos=k_pos,
                              causal=causal, window=window,
                              qc=min(cfg.attn_chunk, s), kc=min(cfg.attn_chunk, t),
                              triangle_skip=triangle_skip, k_live=k_live)
        else:
            o = _sdpa_full(qh, kh, vh, q_pos=q_pos, k_pos=k_pos,
                           causal=causal, window=window, k_live=k_live)

    o2 = o.reshape(b, s, hq * hd)
    o2 = constrain(o2, "batch", None, "model")
    out = o2 @ p["wo"]
    out = constrain(out, "batch", None, None)
    if cache is not None:
        return out, new_cache
    return out


# ---------------------------------------------------------------------------
# FFN
# ---------------------------------------------------------------------------


def init_ffn(key, cfg, d_ff: Optional[int] = None):
    dff = d_ff or cfg.d_ff
    ks = jax.random.split(key, 3)
    p = {"w1": dense_init(ks[0], cfg.d_model, dff, cfg.jdtype),
         "w2": dense_init(ks[1], dff, cfg.d_model, cfg.jdtype)}
    if cfg.ffn_gated:
        p["w3"] = dense_init(ks[2], cfg.d_model, dff, cfg.jdtype)
    return p


def ffn(p, x, cfg, method="autodiff"):
    h = x @ p["w1"]
    h = constrain(h, "batch", None, "model")
    h = rules.act(h, cfg.act, method, cfg.residual_policy)
    if cfg.ffn_gated:
        h = h * (x @ p["w3"])
    out = h @ p["w2"]
    return constrain(out, "batch", None, None)


# ---------------------------------------------------------------------------
# embeddings / head
# ---------------------------------------------------------------------------


def init_embed(key, cfg):
    k1, k2 = jax.random.split(key)
    v = cfg.padded_vocab
    p = {"table": dense_init(k1, v, cfg.d_model, cfg.jdtype, scale=0.02)}
    if not cfg.tie_embeddings:
        p["head"] = dense_init(k2, cfg.d_model, v, cfg.jdtype)
    return p


def embed(p, tokens, cfg):
    """Token lookup from the d-sharded table — explicitly LOCAL gather.

    Expressed as shard_map (table d-sharded on "model", tokens batch-sharded,
    output [B, S, d/16] per shard) so the partitioner can never fall into a
    windowed-gather plan: zero collectives by construction.  Falls back to a
    plain take with no active mesh (CPU smoke paths).
    """
    mesh = current_mesh()
    table = p["table"]
    if mesh is None:
        return jnp.take(table, tokens, axis=0)
    names = set(mesh.axis_names)
    bd = tuple(a for a in ("pod", "data") if a in names)
    dp = 1
    for a in bd:
        dp *= mesh.shape[a]
    tok_spec = (bd if (bd and tokens.shape[0] % dp == 0) else None)
    model = "model" if "model" in names else None
    f = jax.shard_map(
        lambda t, x: jnp.take(t, x, axis=0),
        mesh=mesh,
        in_specs=(P(None, model), P(tok_spec, None)),
        out_specs=P(tok_spec, None, model),
    )
    out = f(table, tokens)
    return constrain(out, "batch", None, "model")


@jax.custom_vjp
def _grad_cast(x):
    """Identity whose backward casts the cotangent to the primal dtype.

    The f32 logits einsum (preferred_element_type) otherwise back-propagates
    an f32 cotangent through the whole residual stream — 2x the backward
    activation HBM traffic and 2x the TP all-reduce bytes (measured: the
    three dominant f32[B,S,d] all-reduces of the train cell).
    """
    return x


def _grad_cast_fwd(x):
    return x, jnp.zeros((0,), x.dtype)


def _grad_cast_bwd(res, g):
    return (g.astype(res.dtype),)


_grad_cast.defvjp(_grad_cast_fwd, _grad_cast_bwd)


def lm_head(p, h, cfg):
    h = _grad_cast(h)
    if cfg.tie_embeddings:
        # Tied table is d-sharded for the lookup; reshard it V-sharded here
        # (a tiny table all-to-all) so the logits einsum contracts the FULL
        # d locally and shards V — avoiding a [B,S,V] all-reduce.
        table = constrain(p["table"], "model", None)
        logits = jnp.einsum("bsd,vd->bsv", h, table,
                            preferred_element_type=jnp.float32)
    else:
        logits = jnp.einsum("bsd,dv->bsv", h, p["head"],
                            preferred_element_type=jnp.float32)
    logits = constrain(logits, "batch", None, "model")
    if cfg.padded_vocab != cfg.vocab:
        logits = logits[..., :cfg.vocab]
    return logits
