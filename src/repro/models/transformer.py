"""Unified backbone covering all ten assigned architectures.

One functional implementation; the config decides per-layer block kinds
(dense attn+FFN, attn+MoE, mamba, hybrid attn||SSM) and the optional
encoder stack (enc-dec audio).  Layers are grouped into homogeneous
*segments* (config.layer_plan) and stacked with ``lax.scan`` over
vmap-initialized params — compile time stays O(segments), not O(layers),
which is what keeps the 512-device dry-run tractable.

Entry points:
  init(key, cfg)                                -> params
  forward(params, cfg, batch, method=...)       -> (logits, aux)   train/eval
  forward_from_embeddings(...)                  -> (logits, aux)   attribution
  init_cache(cfg, batch, capacity, src_len=0)   -> cache pytree
  prefill(params, cfg, batch, cache)            -> (logits, cache)
  decode_step(params, cfg, tokens, cache, pos)  -> (logits, cache)

Caches are per-segment pytrees; mamba segments carry O(1) recurrent state,
which is why the SSM/hybrid archs run the long_500k cell (DESIGN.md §4).
Enc-dec segments additionally cache the per-layer projected cross k/v once
at prefill, so decode never re-touches the encoder.
"""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp

from repro.dist.sharding import constrain
from repro.models import layers, mamba, moe
from repro.models.config import ModelConfig

#: Token id a prompt is LEFT-padded with to its length bucket
#: (``repro.lm.pad_tokens``).
PAD_ID = 0

# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------


def _init_block(key, cfg: ModelConfig, kind: str, cross: bool = False):
    ks = jax.random.split(key, 8)
    p = {"norm1": layers.norm_init(cfg.d_model, cfg.norm)}
    if kind in ("mamba", "mamba_ffn"):
        p["mixer"] = mamba.init_mamba(ks[0], cfg)
        if kind == "mamba":
            return p
    if kind == "hybrid":
        p["attn"] = layers.init_attention(ks[0], cfg)
        p["ssm"] = mamba.init_mamba(ks[1], cfg)
        p["norm_attn"] = layers.norm_init(cfg.d_model, cfg.norm)
        p["norm_ssm"] = layers.norm_init(cfg.d_model, cfg.norm)
    elif kind != "mamba_ffn":
        p["attn"] = layers.init_attention(ks[0], cfg)
    p["norm2"] = layers.norm_init(cfg.d_model, cfg.norm)
    if kind == "moe":
        p["ffn"] = moe.init_moe(ks[2], cfg)
    else:
        p["ffn"] = layers.init_ffn(ks[2], cfg)
    if cross:
        p["cross"] = layers.init_attention(ks[3], cfg)
        p["norm_cross"] = layers.norm_init(cfg.d_model, cfg.norm)
    return p


def _init_segment(key, cfg, kind: str, count: int, cross: bool = False):
    keys = jax.random.split(key, count)
    return jax.vmap(lambda k: _init_block(k, cfg, kind, cross))(keys)


def init(key, cfg: ModelConfig):
    k_embed, k_dec, k_enc, _ = jax.random.split(key, 4)
    params: Dict = {"embed": layers.init_embed(k_embed, cfg),
                    "final_norm": layers.norm_init(cfg.d_model, cfg.norm)}
    seg_keys = jax.random.split(k_dec, len(cfg.layer_plan()))
    params["segments"] = [
        _init_segment(sk, cfg, kind, count, cross=cfg.enc_layers > 0)
        for sk, (kind, count, _) in zip(seg_keys, cfg.layer_plan())
    ]
    if cfg.enc_layers:
        params["encoder"] = _init_segment(k_enc, cfg, "dense", cfg.enc_layers)
        params["enc_norm"] = layers.norm_init(cfg.d_model, cfg.norm)
    return params


# ---------------------------------------------------------------------------
# one layer
# ---------------------------------------------------------------------------


def _cross_attend(p, x, cfg, cache, enc_out, method):
    """Cross-attention with per-layer projected (cached) encoder k/v.

    Returns (delta_x, new_(ck, cv)).  enc_out given => (re)project (train or
    prefill); otherwise read the cached projections (decode).
    """
    b = x.shape[0]
    hd, kvh = cfg.hd, cfg.n_kv
    hc = layers.apply_norm(p["norm_cross"], x, cfg.norm)
    if enc_out is not None:
        ck = (enc_out @ p["cross"]["wk"])
        cv = (enc_out @ p["cross"]["wv"])
    else:
        ck, cv = cache["ck"], cache["cv"]
    k4 = ck.reshape(b, ck.shape[1], kvh, hd)
    v4 = cv.reshape(b, cv.shape[1], kvh, hd)
    c = layers.attention(p["cross"], hc, cfg, rope_cs=None, causal=False,
                         kv_override=(k4, v4), method=method)
    return c, (ck, cv)


def _block(p, x, cfg, kind: str, *, rope_cs, window: int, method: str,
           cache=None, pos=None, enc_out=None, causal=True,
           triangle_skip=True, scan_tile=None, live=None):
    """One layer. Returns (x, new_cache_slice, aux).  ``live`` [B, S]
    (bool) keeps left padding out of every mixer (see
    :func:`forward_from_embeddings`)."""
    aux = jnp.zeros((), jnp.float32)
    h = layers.apply_norm(p["norm1"], x, cfg.norm)
    new_cache = cache

    if kind in ("mamba", "mamba_ffn"):
        with jax.named_scope("mamba"):
            out, new_cache = mamba.mamba_core(p["mixer"], h, cfg, method,
                                              state=cache, pos=pos,
                                              scan_tile=scan_tile, live=live)
        x = x + out
        if kind == "mamba":
            return x, new_cache, aux
    elif kind == "hybrid":
        attn_cache = cache["attn"] if cache is not None else None
        ssm_state = cache["ssm"] if cache is not None else None
        a = layers.attention(p["attn"], h, cfg, rope_cs=rope_cs, causal=causal,
                             window=window, cache=attn_cache, pos=pos,
                             method=method, triangle_skip=triangle_skip,
                             k_live=live)
        if attn_cache is not None:
            a, attn_cache = a
        sout, ssm_state = mamba.mamba_core(p["ssm"], h, cfg, method,
                                           state=ssm_state, pos=pos,
                                           scan_tile=scan_tile, live=live)
        # hymba: mean of per-branch-normalized outputs
        mix = 0.5 * (layers.apply_norm(p["norm_attn"], a, cfg.norm)
                     + layers.apply_norm(p["norm_ssm"], sout, cfg.norm))
        x = x + mix
        if cache is not None:
            new_cache = {"attn": attn_cache, "ssm": ssm_state}
    else:
        self_cache = None
        if cache is not None:
            self_cache = {"k": cache["k"], "v": cache["v"]}
        with jax.named_scope("attention"):
            a = layers.attention(p["attn"], h, cfg, rope_cs=rope_cs,
                                 causal=causal, window=window,
                                 cache=self_cache, pos=pos, method=method,
                                 triangle_skip=triangle_skip, k_live=live)
        if self_cache is not None:
            a, self_cache = a
        x = x + a
        if cache is not None:
            new_cache = dict(cache)
            new_cache.update(self_cache)

    if "cross" in p and (enc_out is not None or
                         (cache is not None and "ck" in cache)):
        c, (ck, cv) = _cross_attend(p, x, cfg, cache, enc_out, method)
        x = x + c
        if cache is not None and "ck" in cache:
            new_cache = dict(new_cache)
            new_cache["ck"], new_cache["cv"] = (
                ck.astype(cache["ck"].dtype), cv.astype(cache["cv"].dtype))

    h2 = layers.apply_norm(p["norm2"], x, cfg.norm)
    if kind == "moe":
        f, aux = moe.moe_ffn(p["ffn"], h2, cfg, method)
    else:
        with jax.named_scope("ffn"):
            f = layers.ffn(p["ffn"], h2, cfg, method)
    return x + f, new_cache, aux


def _remat(fn, cfg):
    if cfg.remat == "none":
        return fn
    policy = (jax.checkpoint_policies.dots_with_no_batch_dims_saveable
              if cfg.remat == "dots" else
              jax.checkpoint_policies.nothing_saveable)
    return jax.checkpoint(fn, policy=policy)


# ---------------------------------------------------------------------------
# stacks
# ---------------------------------------------------------------------------


def _run_segments(params, cfg, x, *, rope_cs, method, caches=None, pos=None,
                  enc_out=None, causal=True, remat=True, triangle_skip=True,
                  scan_tiles=None, live=None):
    """Scan each homogeneous segment; returns (x, new_caches, aux_total).

    ``scan_tiles`` is an optional per-SEGMENT dict ``{si: (d_tile, chunk)}``
    of planned SSM launch knobs (``lax.scan`` stacks the layers within a
    segment, so the knob granularity is the segment, not the layer).
    """
    aux_total = jnp.zeros((), jnp.float32)
    new_caches = [] if caches is not None else None
    for si, (kind, count, window) in enumerate(cfg.layer_plan()):
        seg_p = params["segments"][si]
        seg_c = caches[si] if caches is not None else None
        seg_tile = scan_tiles.get(si) if scan_tiles else None

        def body(carry, xs, kind=kind, window=window,
                 seg_has_cache=seg_c is not None, seg_tile=seg_tile):
            xx, aux_acc = carry
            if seg_has_cache:
                lp, lc = xs
            else:
                lp, lc = xs, None
            xx, nc, aux = _block(lp, xx, cfg, kind, rope_cs=rope_cs,
                                 window=window, method=method, cache=lc,
                                 pos=pos, enc_out=enc_out, causal=causal,
                                 triangle_skip=triangle_skip,
                                 scan_tile=seg_tile, live=live)
            return (xx, aux_acc + aux), nc

        fn = _remat(body, cfg) if remat else body
        xs = (seg_p, seg_c) if seg_c is not None else seg_p
        (x, aux_total), seg_nc = jax.lax.scan(fn, (x, aux_total), xs)
        if new_caches is not None:
            new_caches.append(seg_nc)
    return x, new_caches, aux_total


# ---------------------------------------------------------------------------
# embeddings / frontends (stubs per assignment: precomputed embeddings)
# ---------------------------------------------------------------------------


def _rope(cfg, s: int):
    """RoPE tables for ``s`` positions, or None where attention sees no
    positions (jamba: the Mamba layers carry the order)."""
    if not cfg.rope:
        return None
    return layers.rope_tables(jnp.arange(s), cfg.hd, cfg.rope_theta)


def embed_inputs(params, cfg, batch: Dict, method="autodiff"):
    """Map the (stubbed-frontend) input dict to backbone embeddings.

    dense/moe/ssm/hybrid: {"tokens": [B, S]}          -> [B, S, d]
    vlm:   {"tokens": [B, S-P], "patches": [B, P, d]} -> concat (anyres stub)
    audio: {"frames": [B, S_src, d], "tokens": [B, S_tgt]} -> decoder embeds
    """
    if cfg.frontend == "patches" and "patches" in batch:
        te = layers.embed(params["embed"], batch["tokens"], cfg)
        return jnp.concatenate([batch["patches"].astype(te.dtype), te], axis=1)
    return layers.embed(params["embed"], batch["tokens"], cfg)


def encode(params, cfg, frames, method="autodiff"):
    """Bidirectional encoder over stub frame embeddings -> [B, S_src, d]."""
    x = frames.astype(cfg.jdtype)
    x = constrain(x, "batch", None, None)
    rope_cs = _rope(cfg, x.shape[1])

    def body(carry, lp):
        xx = carry
        h = layers.apply_norm(lp["norm1"], xx, cfg.norm)
        a = layers.attention(lp["attn"], h, cfg, rope_cs=rope_cs,
                             causal=False, method=method)
        xx = xx + a
        h2 = layers.apply_norm(lp["norm2"], xx, cfg.norm)
        xx = xx + layers.ffn(lp["ffn"], h2, cfg, method)
        return xx, None

    fn = jax.checkpoint(body) if cfg.remat != "none" else body
    x, _ = jax.lax.scan(fn, x, params["encoder"])
    return layers.apply_norm(params["enc_norm"], x, cfg.norm)


# ---------------------------------------------------------------------------
# public API
# ---------------------------------------------------------------------------


def forward_from_embeddings(params, cfg: ModelConfig, h, *, method="autodiff",
                            enc_frames=None, remat=True, causal=True,
                            triangle_skip=True, scan_tiles=None,
                            last_only=False, live=None):
    """Backbone from embeddings -> (logits, aux). The attribution entry.

    ``scan_tiles`` routes SSM segments through the planned Pallas scan
    (``{segment_index: (d_tile, chunk)}``); None keeps the XLA chunked scan.
    ``last_only`` computes the head at the last position alone (logits
    ``[B, 1, V]``): next-token attribution needs no other.
    ``live`` [B, S] (bool, from :func:`live_positions`) is False on left
    padding: no attention reads those keys and the Mamba conv and scan see
    zeros there, so every live position computes what it would in the
    unpadded sequence (exactly so where attention has no absolute
    positions) and the padding's embeddings get no gradient.
    """
    h = constrain(h.astype(cfg.jdtype), "batch", None, None)
    s = h.shape[1]
    rope_cs = _rope(cfg, s)
    enc_out = None
    if cfg.enc_layers and enc_frames is not None:
        enc_out = encode(params, cfg, enc_frames, method)
    x, _, aux = _run_segments(params, cfg, h, rope_cs=rope_cs, method=method,
                              enc_out=enc_out, causal=causal, remat=remat,
                              triangle_skip=triangle_skip,
                              scan_tiles=scan_tiles, live=live)
    if last_only:
        x = x[:, -1:]
    x = layers.apply_norm(params["final_norm"], x, cfg.norm)
    logits = layers.lm_head(params["embed"], x, cfg)
    return logits, aux


def live_positions(tokens, n_embedded: int, pad_id: int = PAD_ID):
    """[B, S] token ids -> [B, n_embedded] bool: False on the run of
    ``pad_id`` that left-pads a prompt, True from its first other token on
    (and on embeddings a frontend puts before the tokens)."""
    live = jnp.cumsum((tokens != pad_id).astype(jnp.int32), axis=-1) > 0
    extra = n_embedded - live.shape[-1]
    if extra:
        live = jnp.pad(live, ((0, 0), (extra, 0)), constant_values=True)
    return live


def forward(params, cfg: ModelConfig, batch: Dict, *, method="autodiff",
            remat=True, triangle_skip=True):
    """Training/eval forward: (logits, aux)."""
    h = embed_inputs(params, cfg, batch, method)
    enc_frames = batch.get("frames") if cfg.enc_layers else None
    return forward_from_embeddings(params, cfg, h, method=method,
                                   enc_frames=enc_frames, remat=remat,
                                   triangle_skip=triangle_skip)


# ---------------------------------------------------------------------------
# serving: cache init / prefill / decode
# ---------------------------------------------------------------------------


def init_cache(cfg: ModelConfig, batch: int, capacity: int, src_len: int = 0):
    """Per-segment cache pytree (fused kv layout, f32 ssm state)."""
    caches = []
    for kind, count, _ in cfg.layer_plan():
        kv_shape = (count, batch, capacity, cfg.n_kv * cfg.hd)
        attn_c = {"k": jnp.zeros(kv_shape, cfg.jdtype),
                  "v": jnp.zeros(kv_shape, cfg.jdtype)}
        if cfg.enc_layers and src_len:
            cross_shape = (count, batch, src_len, cfg.n_kv * cfg.hd)
            attn_c["ck"] = jnp.zeros(cross_shape, cfg.jdtype)
            attn_c["cv"] = jnp.zeros(cross_shape, cfg.jdtype)
        ssm_c = {
            "h": jnp.zeros((count, batch, cfg.d_inner, cfg.ssm_state),
                           jnp.float32),
            "conv": jnp.zeros((count, batch, cfg.ssm_conv - 1, cfg.d_inner),
                              cfg.jdtype),
        }
        if kind in ("mamba", "mamba_ffn"):
            caches.append(ssm_c)
        elif kind == "hybrid":
            caches.append({"attn": attn_c, "ssm": ssm_c})
        else:
            caches.append(attn_c)
    return caches


def prefill(params, cfg: ModelConfig, batch: Dict, cache, *,
            method="autodiff", triangle_skip=True):
    """Fill caches from a full prompt; returns (last-position logits, cache)."""
    h = embed_inputs(params, cfg, batch, method)
    h = constrain(h.astype(cfg.jdtype), "batch", None, None)
    s = h.shape[1]
    rope_cs = _rope(cfg, s)
    enc_out = None
    if cfg.enc_layers and "frames" in batch:
        enc_out = encode(params, cfg, batch["frames"], method)
    x, new_caches, _ = _run_segments(params, cfg, h, rope_cs=rope_cs,
                                     method=method, caches=cache, pos=None,
                                     enc_out=enc_out, remat=False,
                                     triangle_skip=triangle_skip)
    x = layers.apply_norm(params["final_norm"], x[:, -1:], cfg.norm)
    logits = layers.lm_head(params["embed"], x, cfg)
    return logits, new_caches


def decode_step(params, cfg: ModelConfig, tokens, cache, pos, *,
                method="autodiff"):
    """One decode step: tokens [B, 1] at position ``pos`` (traced scalar)."""
    h = layers.embed(params["embed"], tokens, cfg)
    # rope_cs=(): sentinel "non-None" — decode builds tables from ``pos``.
    x, new_caches, _ = _run_segments(params, cfg, h,
                                     rope_cs=() if cfg.rope else None,
                                     method=method,
                                     caches=cache, pos=pos, remat=False)
    x = layers.apply_norm(params["final_norm"], x, cfg.norm)
    logits = layers.lm_head(params["embed"], x, cfg)
    return logits, new_caches
