"""Token-choice top-k MoE with DATA-LOCAL sort-based capacity dispatch.

Design goals (DESIGN.md §7.4 + EXPERIMENTS.md §Perf hillclimb #1):

  * activated-FLOPs-faithful — tokens are *routed*, never run through every
    expert, so the roofline compute term reflects 6·N_active·D;
  * EP-shardable — experts live on the "model" mesh axis;
  * dispatch locality — routing, sort and capacity are computed PER DATA
    SHARD along an explicit leading shard axis.  The token activations are
    already replicated across the "model" axis (batch shards on data only),
    so gathering [shard, E, C_local, d] — sharded (data, model) — moves ZERO
    bytes; the combine's expert partial sums reduce with the same
    row-parallel all-reduce any FFN output has.  The GSPMD-auto *global*
    dispatch this replaces all-gathered the full token buffer per layer:
    124 s collective term vs 3.2 s compute on llama4-scout train_4k.
    Per-shard capacity matches deployed-MoE semantics (per-device drops).
  * dense-shape static — C_local = ceil(T_local·k/E · cf); overflow drops,
    underflow pads with zeros.

The expert gates go through rules.act so attribution BP crosses the MoE with
the configured method; the hard top-k dispatch indices are themselves the
paper's "cheapest sufficient residual" — BP routing needs indices, not
activations.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import rules
from repro.dist.sharding import constrain, current_mesh
from repro.models import layers


def init_moe(key, cfg):
    e, d, f = cfg.n_experts, cfg.d_model, cfg.d_ff
    ks = jax.random.split(key, 5)
    s = (2.0 / (d + f)) ** 0.5

    def ew(k, a, b_):
        return (jax.random.normal(k, (e, a, b_), jnp.float32) * s).astype(cfg.jdtype)

    p = {
        "router": layers.dense_init(ks[0], d, e, jnp.float32, scale=0.02),
        "w1": ew(ks[1], d, f),
        "w2": ew(ks[2], f, d),
    }
    if cfg.ffn_gated:
        p["w3"] = ew(ks[3], d, f)
    if cfg.n_shared_experts:
        p["shared"] = layers.init_ffn(ks[4], cfg,
                                      d_ff=cfg.d_ff * cfg.n_shared_experts)
    return p


def _capacity(t_local: int, cfg) -> int:
    c = int(t_local * cfg.top_k * cfg.capacity_factor / cfg.n_experts)
    return max(8, -(-c // 8) * 8)


def _data_shards(x) -> int:
    """Product of the mesh's DP axes when the token count divides it."""
    mesh = current_mesh()
    if mesh is None:
        return 1
    dp = 1
    for ax in ("pod", "data"):
        if ax in mesh.axis_names:
            dp *= mesh.shape[ax]
    t = x.shape[0] * x.shape[1]
    return dp if (dp > 1 and t % dp == 0 and t // dp >= 8) else 1


def _bd_axes(mesh):
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def _local_take(xpad, tok_slots):
    """take_along_axis with the shard axis pinned LOCAL via shard_map.

    GSPMD's gather partitioner all-gathers the f32 token buffer otherwise
    (measured 1 TB/device on scout train: f32[16,8193,5120] all-gather x384).
    Forward AND its transpose (scatter-add) stay shard-local here.
    """
    mesh = current_mesh()
    bd = _bd_axes(mesh) if mesh is not None else ()
    dp = 1
    for ax in bd:
        dp *= mesh.shape[ax]
    if mesh is None or xpad.shape[0] % max(dp, 1) != 0 or dp == 1:
        return jnp.take_along_axis(xpad, tok_slots[..., None], axis=1)
    from jax.sharding import PartitionSpec as P
    f = jax.shard_map(
        lambda xp, ts: jnp.take_along_axis(xp, ts[..., None], axis=1),
        mesh=mesh,
        in_specs=(P(bd, None, None), P(bd, None)),
        out_specs=P(bd, None, None),
    )
    return f(xpad, tok_slots)


def _local_combine(yw, tok_slots, t: int):
    """Gate-weighted scatter-add back to [D, T+1, d], shard-local."""
    mesh = current_mesh()

    def scatter(yw_, ts):
        ds_, _, d_ = yw_.shape
        rows = jnp.arange(ds_)[:, None]
        out = jnp.zeros((ds_, t + 1, d_), yw_.dtype)
        return out.at[rows, ts].add(yw_, mode="drop")

    bd = _bd_axes(mesh) if mesh is not None else ()
    dp = 1
    for ax in bd:
        dp *= mesh.shape[ax]
    if mesh is None or yw.shape[0] % max(dp, 1) != 0 or dp == 1:
        return scatter(yw, tok_slots)
    from jax.sharding import PartitionSpec as P
    f = jax.shard_map(
        scatter, mesh=mesh,
        in_specs=(P(bd, None, None), P(bd, None)),
        out_specs=P(bd, None, None),
    )
    return f(yw, tok_slots)


def moe_ffn(p, x, cfg, method="autodiff"):
    """x: [B, S, d] -> (out [B, S, d], aux_loss)."""
    b, s, d = x.shape
    shards = _data_shards(x)
    xt = x.reshape(shards, (b * s) // shards, d)
    xt = constrain(xt, "batch", None, None)

    ds, t = xt.shape[0], xt.shape[1]
    e, k = cfg.n_experts, cfg.top_k
    c = _capacity(t, cfg)

    # ---- routing (f32) ----
    logits = jnp.einsum("xtd,de->xte", xt.astype(jnp.float32), p["router"])
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, expert_ids = jax.lax.top_k(probs, k)          # [D, T, k]
    gate_vals = gate_vals / jnp.maximum(
        jnp.sum(gate_vals, axis=-1, keepdims=True), 1e-9)

    # load-balancing aux (Shazeer-style), averaged over shards
    me = jnp.mean(probs, axis=(0, 1))
    ce = jnp.mean(jax.nn.one_hot(expert_ids[..., 0], e, dtype=jnp.float32),
                  axis=(0, 1))
    aux = cfg.router_aux_coef * e * jnp.sum(me * ce)

    # ---- per-shard sort-based capacity dispatch (ALL ops local per row) ----
    tk = t * k
    flat_ids = expert_ids.reshape(ds, tk)
    flat_gate = gate_vals.reshape(ds, tk)
    flat_tok = jnp.broadcast_to(jnp.repeat(jnp.arange(t), k)[None], (ds, tk))

    order = jnp.argsort(flat_ids, axis=-1)                   # stable per shard
    s_ids = jnp.take_along_axis(flat_ids, order, axis=-1)
    s_tok = jnp.take_along_axis(flat_tok, order, axis=-1)
    s_gate = jnp.take_along_axis(flat_gate, order, axis=-1)

    counts = jnp.sum(jax.nn.one_hot(flat_ids, e, dtype=jnp.int32), axis=1)
    start = jnp.cumsum(counts, axis=-1) - counts             # [D, E]
    pos_in_e = (jnp.arange(tk)[None]
                - jnp.take_along_axis(start, s_ids, axis=-1))
    keep = pos_in_e < c

    slot = s_ids * c + jnp.where(keep, pos_in_e, 0)          # [D, T*k]
    rows = jnp.arange(ds)[:, None]
    tok_slots = jnp.full((ds, e * c), t, jnp.int32)
    tok_slots = tok_slots.at[rows, slot].set(
        jnp.where(keep, s_tok, t).astype(jnp.int32), mode="drop")
    gate_slots = jnp.zeros((ds, e * c), jnp.float32)
    gate_slots = gate_slots.at[rows, slot].set(
        jnp.where(keep, s_gate, 0.0), mode="drop")

    xpad = jnp.concatenate([xt, jnp.zeros((ds, 1, d), xt.dtype)], axis=1)
    xe = _local_take(xpad, tok_slots)
    xe = xe.reshape(ds, e, c, d)
    # [shard, E, C, d]: data axes on shard, EP on experts — the dispatch
    # gather above is LOCAL (tokens replicated over "model")
    xe = constrain(xe, "batch", "expert", None, None)

    # ---- expert compute (activated FLOPs: D*E*C ~= T_global*k*cf rows) ----
    h = jnp.einsum("xecd,edf->xecf", layers._grad_cast(xe), p["w1"],
                   preferred_element_type=jnp.float32).astype(xe.dtype)
    h = constrain(h, "batch", "expert", None, None)
    h = rules.act(h, cfg.act, method, cfg.residual_policy)
    if cfg.ffn_gated:
        h = h * jnp.einsum("xecd,edf->xecf", layers._grad_cast(xe), p["w3"],
                           preferred_element_type=jnp.float32).astype(xe.dtype)
    y = jnp.einsum("xecf,efd->xecd", layers._grad_cast(h), p["w2"],
                   preferred_element_type=jnp.float32).astype(xe.dtype)
    y = constrain(y, "batch", "expert", None, None)

    # ---- combine: gate-weighted scatter-add back to tokens (shard-local;
    # the expert-sharded y all-gathers over "model" once — 0.1 GB/layer vs
    # the TB-scale GSPMD scatter it replaces). Gate-weighting happens in the
    # compute dtype: an f32 carrier here doubled the all-gather wire bytes
    # (§Perf It.8). ----
    yw = y.reshape(ds, e * c, d) * gate_slots[..., None].astype(y.dtype)
    yw = constrain(yw, "batch", None, None)
    out = _local_combine(yw, tok_slots, t)
    out = constrain(out[:, :t], "batch", None, None).reshape(b, s, d)

    if cfg.n_shared_experts:
        out = out + layers.ffn(p["shared"], x, cfg, method)

    return constrain(out, "batch", None, None), aux
