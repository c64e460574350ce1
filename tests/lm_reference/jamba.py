"""A plain float32 reference of AI21-Jamba2-3B and its token attribution.

Written from the model's published config (the keys of ``dims`` are its
``config.json`` names) and the ``transformers`` Jamba modelling, not from
the program, and imports nothing of it.  Every matmul runs in float32 under
``jax.default_matmul_precision("highest")``.

* embedding lookup (no scaling);
* layer ``i`` is attention where ``i % attn_layer_period ==
  attn_layer_offset`` and Mamba otherwise; each layer is
  ``h = x + mixer(RMSNorm(x))`` then ``h + SwiGLU(RMSNorm(h))``;
* Mamba-1: in_proj to x and z; a causal depthwise conv (kernel
  ``mamba_d_conv``, with bias) and SiLU on x; x_proj to dt, B, C, each
  RMS-normed; ``dt = softplus(dt_proj(dt) + dt_bias)``; the plain
  recurrence ``h_t = exp(dt_t A) h_{t-1} + dt_t x_t B_t``,
  ``y_t = <h_t, C_t> + D x_t``; gated by ``SiLU(z)``; out_proj;
* attention: causal multi-query attention with no positional encoding,
  scores scaled by ``head_dim ** -0.5``;
* final RMSNorm and the tied head (the embedding table transposed).

Token attribution explains the argmax logit of the last position: the
gradient of that logit with respect to the input embeddings, reduced over
the hidden size as its L2 norm (``token_saliency``) or as the sum of
embedding times gradient (``token_ixg``).

Departures from ``JambaForCausalLM``: the weights are whatever the caller
passes (read from the program's parameter tree, whose layout is data:
``params["segments"]`` stacks each run of same-kind layers;
:func:`random_params` draws such a tree); a prompt is explained without
padding (callers pass its own tokens, as an attention mask would leave
them); the head is computed at the last position alone
(``num_logits_to_keep`` 1).
"""
from __future__ import annotations

from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp

#: the published config's keys this reference reads
KEYS = ("num_hidden_layers", "hidden_size", "num_attention_heads",
        "num_key_value_heads", "intermediate_size", "vocab_size",
        "mamba_d_state", "mamba_d_conv", "mamba_expand", "mamba_dt_rank",
        "attn_layer_period", "attn_layer_offset", "rms_norm_eps")

MODES = {"token_saliency": "saliency", "token_ixg": "ixg"}


def layer_kinds(dims: dict) -> List[str]:
    return ["attention"
            if i % dims["attn_layer_period"] == dims["attn_layer_offset"]
            else "mamba" for i in range(dims["num_hidden_layers"])]


def layer_params(params, dims: dict) -> List[Tuple[str, Dict]]:
    """(kind, that layer's weights) in order: each run of same-kind layers
    is one stacked entry of ``params["segments"]``."""
    out, seg, j = [], -1, 0
    kinds = layer_kinds(dims)
    for i, kind in enumerate(kinds):
        if i == 0 or kind != kinds[i - 1]:
            seg, j = seg + 1, 0
        out.append((kind, jax.tree.map(lambda a: a[j],
                                       params["segments"][seg])))
        j += 1
    return out


def rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def silu(x):
    return x * jax.nn.sigmoid(x)


def mamba(p, x, dims):
    """x [S, d] -> [S, d]."""
    n, k, dtr = dims["mamba_d_state"], dims["mamba_d_conv"], \
        dims["mamba_dt_rank"]
    eps = dims["rms_norm_eps"]
    s = x.shape[0]
    xin, z = jnp.split(x @ p["in_proj"], 2, axis=-1)
    xp = jnp.concatenate([jnp.zeros((k - 1, xin.shape[1])), xin])
    xc = silu(sum(xp[i:i + s] * p["conv_w"][i] for i in range(k))
              + p["conv_b"])
    dt_r, bm, cm = jnp.split(xc @ p["x_proj"], [dtr, dtr + n], axis=-1)
    dt_r = rmsnorm(dt_r, p["dt_norm"]["w"], eps)
    bm = rmsnorm(bm, p["b_norm"]["w"], eps)
    cm = rmsnorm(cm, p["c_norm"]["w"], eps)
    dt = jax.nn.softplus(dt_r @ p["dt_proj"] + p["dt_bias"])   # [S, di]
    a = -jnp.exp(p["A_log"])                                   # [di, N]

    def step(h, inp):
        dt_t, x_t, b_t, c_t = inp
        h = jnp.exp(dt_t[:, None] * a) * h + (dt_t * x_t)[:, None] * b_t
        return h, h @ c_t

    _, y = jax.lax.scan(step, jnp.zeros(a.shape), (dt, xc, bm, cm))
    y = (y + xc * p["D"]) * silu(z)
    return y @ p["out_proj"]


def attention(p, x, dims):
    """Causal multi-query attention, no positional encoding."""
    s = x.shape[0]
    heads, kv = dims["num_attention_heads"], dims["num_key_value_heads"]
    hd = dims["hidden_size"] // heads
    q = (x @ p["wq"]).reshape(s, heads, hd)
    k = (x @ p["wk"]).reshape(s, kv, hd)
    v = (x @ p["wv"]).reshape(s, kv, hd)
    k = jnp.repeat(k, heads // kv, axis=1)
    v = jnp.repeat(v, heads // kv, axis=1)
    scores = jnp.einsum("qhd,khd->hqk", q, k) * hd ** -0.5
    causal = jnp.tril(jnp.ones((s, s), bool))
    scores = jnp.where(causal[None], scores, -jnp.inf)
    o = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, axis=-1), v)
    return o.reshape(s, heads * hd) @ p["wo"]


def swiglu(p, x):
    return (silu(x @ p["w1"]) * (x @ p["w3"])) @ p["w2"]


def logits_from_embeddings(params, dims, e):
    """e [S, d] -> last-position logits [V]."""
    eps = dims["rms_norm_eps"]
    x = e
    for kind, p in layer_params(params, dims):
        h = rmsnorm(x, p["norm1"]["w"], eps)
        x = x + (attention(p["attn"], h, dims) if kind == "attention"
                 else mamba(p["mixer"], h, dims))
        x = x + swiglu(p["ffn"], rmsnorm(x, p["norm2"]["w"], eps))
    x = rmsnorm(x[-1], params["final_norm"]["w"], eps)
    table = params["embed"]["table"][:dims["vocab_size"]]
    return table @ x


def explain(params, dims: dict, tokens) -> Tuple[jnp.ndarray, Dict]:
    """tokens [S] -> (last-position logits [V], {method: scores [S]})."""
    with jax.default_matmul_precision("highest"):
        e = params["embed"]["table"][jnp.asarray(tokens)]
        logits = logits_from_embeddings(params, dims, e)
        target = jnp.argmax(logits)
        g = jax.grad(lambda v: logits_from_embeddings(params, dims, v)
                     [target])(e)
    return logits, {"token_saliency": jnp.linalg.norm(g, axis=-1),
                    "token_ixg": jnp.sum(e * g, axis=-1)}


#: the dense matrices, drawn N(0, 2 / (fan_in + fan_out))
MATRICES = ("in_proj", "x_proj", "dt_proj", "out_proj", "wq", "wk", "wv",
            "wo", "w1", "w2", "w3")


def random_params(key, like):
    """Random weights in the layout of ``like`` (a tree of arrays or
    shapes), every channel its own: RMSNorm weights 1 + N(0, 0.1), the
    conv bias N(0, 0.1), ``dt_bias`` the inverse softplus of a
    log-uniform step in [1e-3, 1e-1], ``exp(A_log)`` uniform in
    [1, d_state], ``D`` uniform in [0.5, 1.5], conv taps N(0, 1) /
    d_conv, the embedding N(0, 0.02)."""
    leaves, tree = jax.tree_util.tree_flatten_with_path(like)
    out = []
    for i, (path, leaf) in enumerate(leaves):
        k, shape, name = jax.random.fold_in(key, i), leaf.shape, \
            path[-1].key
        normal = jax.random.normal(k, shape)
        if name == "w":
            v = 1.0 + 0.1 * normal
        elif name in MATRICES:
            v = normal * (2.0 / (shape[-2] + shape[-1])) ** 0.5
        elif name == "table":
            v = 0.02 * normal
        elif name == "conv_w":
            v = normal / shape[-2]
        elif name == "conv_b":
            v = 0.1 * normal
        elif name == "dt_bias":
            step = jnp.exp(jax.random.uniform(k, shape, minval=jnp.log(1e-3),
                                              maxval=jnp.log(1e-1)))
            v = step + jnp.log(-jnp.expm1(-step))
        elif name == "A_log":
            v = jnp.log(jax.random.uniform(k, shape, minval=1.0,
                                           maxval=float(shape[-1])))
        elif name == "D":
            v = jax.random.uniform(k, shape, minval=0.5, maxval=1.5)
        else:
            raise ValueError(f"no draw for weight {name!r}")
        out.append(v.astype(jnp.float32))
    return jax.tree_util.tree_unflatten(tree, out)


def dims_of(cfg) -> dict:
    """The published config's names for a program configuration's sizes
    (the only place the reference reads the program's configuration)."""
    return {"num_hidden_layers": cfg.n_layers, "hidden_size": cfg.d_model,
            "num_attention_heads": cfg.n_heads,
            "num_key_value_heads": cfg.n_kv,
            "intermediate_size": cfg.d_ff, "vocab_size": cfg.vocab,
            "mamba_d_state": cfg.ssm_state, "mamba_d_conv": cfg.ssm_conv,
            "mamba_expand": cfg.ssm_expand, "mamba_dt_rank": cfg.dtr,
            "attn_layer_period": cfg.attn_period,
            "attn_layer_offset": cfg.attn_offset, "rms_norm_eps": 1e-6}
