"""AI21-Jamba2-3B's token attribution as the harness reaches it.

The system under test is the program's LM path, ``EngineSpec -> build ->
LMAdapter -> ExplanationServer``, at the configuration's precision and
seats.  A request is one prompt, left-padded to its pow2 length bucket
(``repro.lm.pad_tokens``); its answer is the last position's logits and
one score per prompt position for the argmax next token (``token_saliency``:
the L2 norm of the embedding gradient; ``token_ixg``: embedding times
gradient, summed over the hidden size).

The configuration's ``model`` block names the program's preset (``arch``,
``size``) and gives the published config's sizes under their own names;
the preset is checked against them.  The mix gives the prompt lengths
(``lengths``: a log-normal of ``median`` and ``sigma`` clipped to
``[min, max]``), its quantiles in a fixed order, the same in every run;
``--seed`` draws the token ids, from ``1 .. vocab_size - 1`` (0 is the pad
id).

The weights are random, drawn here from the configuration's
``weight_seed`` with every channel its own (``init_params``); only their
layout is the program's.

The plain reference (``explain_rows``) is written from the published
config and the ``transformers`` Jamba modelling and imports nothing of the
program; it runs after the window, once the harness has dropped the
server.  It explains each prompt's own tokens, without the bucket's left
padding (``prompt``), as ``JambaForCausalLM`` does with an attention mask;
the program's scores at the padded positions are held to zero.  It computes layer by layer, each layer's forward and backward its
own jitted program (the weights it needs are arguments), so what it holds
at once is the weights, the layer inputs and one layer's backward.  Every
prompt of a group is placed at the end of one buffer as long as the
group's longest and the positions before it are masked out of every mixer
(their conv inputs and scan inputs are zero, attention never reads them),
so one set of programs serves every length.  The scan is a chunked
associative scan.  ``mode`` sets the precision of every dot: ``highest``
for the comparison, ``high`` (three bfloat16 passes) for the control.
"""
from __future__ import annotations

import functools
import math
import statistics
from dataclasses import dataclass
from typing import Dict, List, Optional, Tuple

import numpy as np

from chipbench import cell, traffic
from chipbench.stats import percentile

#: the relevance percentiles reported (one or more are held to limits)
RELEVANCE_PERCENTILES = (50, 90, 100)
#: the stream that orders the prompt lengths (the same in every run)
LENGTH_SEED, LENGTH_STREAM = 0, 5
#: the reference scan's chunk: an associative scan inside, a sequential
#: carry across
REF_CHUNK = 16
#: the program's pad id (``repro.lm.PAD_ID``); token ids avoid it
PAD_ID = 0


def _cfg(model: dict):
    """The program's preset at the configuration's depth, computing in
    float32; refused when its sizes are not the model block's."""
    import repro.configs as configs
    base = (configs.get_smoke(model["arch"]) if model.get("size") == "smoke"
            else configs.get(model["arch"]))
    cfg = base.with_(n_layers=int(model["num_hidden_layers"]),
                     dtype="float32")
    have = {"hidden_size": cfg.d_model, "num_attention_heads": cfg.n_heads,
            "num_key_value_heads": cfg.n_kv, "head_dim": cfg.hd,
            "intermediate_size": cfg.d_ff, "vocab_size": cfg.vocab,
            "mamba_d_state": cfg.ssm_state, "mamba_d_conv": cfg.ssm_conv,
            "mamba_expand": cfg.ssm_expand, "mamba_dt_rank": cfg.dtr,
            "attn_layer_period": cfg.attn_period,
            "attn_layer_offset": cfg.attn_offset}
    wrong = {k: (v, model[k]) for k, v in have.items() if model[k] != v}
    if wrong:
        raise cell.RefusedError(f"preset {model['arch']} is not the "
                                f"configuration's model: {wrong}")
    return cfg


def weight_shapes(model: dict) -> dict:
    """The weights' shapes from the model block, in the tree the program
    reads (``repro.models.transformer``'s layout, which is data: each run
    of same-kind layers is one entry of ``segments``, its leaves stacked
    over the run's layers)."""
    d, ff, v = (model["hidden_size"], model["intermediate_size"],
                model["vocab_size"])
    di = model["mamba_expand"] * d
    n, k, r = (model["mamba_d_state"], model["mamba_d_conv"],
               model["mamba_dt_rank"])
    q = model["num_attention_heads"] * model["head_dim"]
    kv = model["num_key_value_heads"] * model["head_dim"]
    runs: List[Tuple[str, int]] = []
    for kind, seg, _ in _layer_index(model):
        if seg == len(runs):
            runs.append((kind, 0))
        runs[seg] = (kind, runs[seg][1] + 1)
    segments = []
    for kind, c in runs:
        seg = {"norm1": {"w": (c, d)}, "norm2": {"w": (c, d)},
               "ffn": {"w1": (c, d, ff), "w2": (c, ff, d),
                       "w3": (c, d, ff)}}
        if kind == "attention":
            seg["attn"] = {"wq": (c, d, q), "wk": (c, d, kv),
                           "wv": (c, d, kv), "wo": (c, q, d)}
        else:
            seg["mixer"] = {
                "in_proj": (c, d, 2 * di), "conv_w": (c, k, di),
                "conv_b": (c, di), "x_proj": (c, di, r + 2 * n),
                "dt_norm": {"w": (c, r)}, "b_norm": {"w": (c, n)},
                "c_norm": {"w": (c, n)}, "dt_proj": (c, r, di),
                "dt_bias": (c, di), "A_log": (c, di, n), "D": (c, di),
                "out_proj": (c, di, d)}
        segments.append(seg)
    return {"embed": {"table": (v, d)}, "final_norm": {"w": (d,)},
            "segments": segments}


#: the dense matrices, drawn N(0, 2 / (fan_in + fan_out))
MATRICES = ("in_proj", "x_proj", "dt_proj", "out_proj", "wq", "wk", "wv",
            "wo", "w1", "w2", "w3")


def _draw(key, name: str, shape: tuple):
    """One leaf, every channel its own: RMSNorm weights 1 + N(0, 0.1);
    the conv bias N(0, 0.1); ``dt_bias`` the inverse softplus of a
    log-uniform step in [1e-3, 1e-1] (Mamba's initialisation, per
    channel); ``A = exp(A_log)`` uniform in [1, d_state] per channel and
    state; ``D`` uniform in [0.5, 1.5]; the conv taps N(0, 1) / d_conv;
    the embedding N(0, 0.02)."""
    import jax
    import jax.numpy as jnp
    f32 = jnp.float32
    if name == "w":
        return 1.0 + 0.1 * jax.random.normal(key, shape, f32)
    if name in MATRICES:
        return (jax.random.normal(key, shape, f32)
                * math.sqrt(2.0 / (shape[-2] + shape[-1])))
    if name == "table":
        return 0.02 * jax.random.normal(key, shape, f32)
    if name == "conv_w":
        return jax.random.normal(key, shape, f32) / shape[-2]
    if name == "conv_b":
        return 0.1 * jax.random.normal(key, shape, f32)
    if name == "dt_bias":
        step = jnp.exp(jax.random.uniform(key, shape, f32, math.log(1e-3),
                                          math.log(1e-1)))
        return step + jnp.log(-jnp.expm1(-step))
    if name == "A_log":
        return jnp.log(jax.random.uniform(key, shape, f32, 1.0,
                                          float(shape[-1])))
    if name == "D":
        return jax.random.uniform(key, shape, f32, 0.5, 1.5)
    raise ValueError(f"no draw for weight {name!r}")


def init_params(model: dict, weight_seed: int):
    """Random weights from the seed, made on the device in one program,
    each leaf from its own key (``weight_seed`` folded with the leaf's
    place in the tree); the preset is checked against the model block
    first."""
    import jax
    _cfg(model)
    shapes = weight_shapes(model)
    paths, tree = jax.tree_util.tree_flatten_with_path(
        shapes, is_leaf=lambda v: isinstance(v, tuple))

    def draw(key):
        return jax.tree_util.tree_unflatten(tree, [
            _draw(jax.random.fold_in(key, i), path[-1].key, shape)
            for i, (path, shape) in enumerate(paths)])

    return jax.jit(draw)(jax.random.PRNGKey(int(weight_seed) & 0x7FFFFFFF))


def build_adapter(config: dict, params, chips: int):
    from repro import lm
    return lm.LMAdapter(params, _cfg(config["model"]),
                        precision=config["precision"],
                        device=cell.engine_device(config, chips),
                        max_batch=int(config["seats"]))


def prompt_lengths(mix: dict, n: int) -> np.ndarray:
    """The live lengths of a run's ``n`` prompts: the log-normal's ``n``
    quantiles, clipped, in an order one fixed stream shuffles, so every
    window holds the mix's proportions whatever its length (as
    ``traffic.exponential_set`` does for the arrivals)."""
    spec = mix["lengths"]
    normal = statistics.NormalDist()
    z = np.array([normal.inv_cdf((i + 0.5) / n) for i in range(n)])
    raw = np.exp(math.log(spec["median"]) + spec["sigma"] * z)
    lengths = np.clip(np.rint(raw), spec["min"], spec["max"]).astype(int)
    return traffic.rng(LENGTH_SEED, LENGTH_STREAM).permutation(lengths)


def bucket(n: int) -> int:
    """The program's pow2 length bucket of an ``n``-token prompt."""
    from repro import lm
    return lm.bucket_len(n)


def payloads(model: dict, mix: dict, seed: int, n: int) -> List[np.ndarray]:
    """``n`` prompts, each left-padded to its bucket with the pad id."""
    r = traffic.rng(seed, 4)
    out = []
    for s in prompt_lengths(mix, n):
        toks = r.integers(1, model["vocab_size"], size=int(s),
                          dtype=np.int32)
        row = np.full(bucket(int(s)), PAD_ID, np.int32)
        row[row.size - s:] = toks
        out.append(row)
    return out


def warm_payloads(plan: traffic.Plan, fill_target: int):
    """Per length bucket the plan sends, ``fill_target`` of its prompts."""
    by_len: Dict[int, List[np.ndarray]] = {}
    for p in plan.payloads:
        by_len.setdefault(p.shape[-1], []).append(p)
    return [[group[j % len(group)] for j in range(fill_target)]
            for _, group in sorted(by_len.items())]


def live_tokens(payload: np.ndarray) -> int:
    return int(np.count_nonzero(np.asarray(payload) != PAD_ID))


def prompt(payload: np.ndarray) -> np.ndarray:
    """A payload's prompt: its tokens after the left padding."""
    x = np.asarray(payload)
    return x[x.size - live_tokens(x):]


def _placed(scores: np.ndarray, size: int) -> np.ndarray:
    """A prompt's scores at the end of a row of ``size``, zeros before:
    what the program answers for the left-padded payload."""
    out = np.zeros(size, np.float32)
    out[size - scores.size:] = scores
    return out


@dataclass
class Served:
    """One answer as the client got it (host arrays)."""
    kind: str
    method: Optional[str]
    x: np.ndarray                      # [S] token ids, left-padded
    logits: np.ndarray                 # [vocab], last position
    target: int = -1
    relevance: Optional[np.ndarray] = None   # [S]


def served(kind: str, payload: np.ndarray, resp) -> Served:
    return Served(kind=kind, method=resp.method, x=np.asarray(payload),
                  logits=np.asarray(resp.logits, np.float32).reshape(-1),
                  target=int(resp.targets[0]),
                  relevance=np.asarray(resp.relevance,
                                       np.float32).reshape(-1))


# -- the plain reference ------------------------------------------------------

def _kinds(model: dict) -> List[str]:
    p, o = model["attn_layer_period"], model["attn_layer_offset"]
    return ["attention" if i % p == o else "mamba"
            for i in range(model["num_hidden_layers"])]


def _layer_index(model: dict) -> List[Tuple[str, int, int]]:
    """(kind, segment, index in segment) per layer: the program's weights
    stack each run of same-kind layers into one entry of ``segments``."""
    out, seg, j, kinds = [], -1, 0, _kinds(model)
    for i, k in enumerate(kinds):
        if i == 0 or k != kinds[i - 1]:
            seg, j = seg + 1, 0
        out.append((k, seg, j))
        j += 1
    return out


def _rmsnorm(x, w, eps):
    import jax
    import jax.numpy as jnp
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _silu(x):
    import jax
    return x * jax.nn.sigmoid(x)


def _scan(dt, xc, bm, cm, a):
    """h_t = exp(dt_t A) h_{t-1} + dt_t x_t B_t, y_t = <h_t, C_t>, from
    h_0 = 0: an associative scan inside each chunk of ``REF_CHUNK`` steps
    (recomputed on the way back), the state carried across chunks.
    dt, xc [T, D]; bm, cm [T, N]; a [D, N] -> y [T, D]."""
    import jax
    import jax.numpy as jnp
    t, d = dt.shape
    n = a.shape[1]
    k = REF_CHUNK
    chunks = lambda v: v.reshape((t // k, k) + v.shape[1:])
    a_nd = a.T                                            # [N, D]

    @jax.checkpoint
    def chunk(h, inp):
        dt_c, x_c, b_c, c_c = inp
        abar = jnp.exp(dt_c[:, None, :] * a_nd)           # [k, N, D]
        u = b_c[:, :, None] * (dt_c * x_c)[:, None, :]

        def comb(l, r):
            return l[0] * r[0], r[0] * l[1] + r[1]

        decay, acc = jax.lax.associative_scan(comb, (abar, u), axis=0)
        hs = decay * h + acc
        return hs[-1], jnp.einsum("knd,kn->kd", hs, c_c)

    _, y = jax.lax.scan(chunk, jnp.zeros((n, d), jnp.float32),
                        (chunks(dt), chunks(xc), chunks(bm), chunks(cm)))
    return y.reshape(t, d)


def _mamba(p, x, live, m):
    import jax
    import jax.numpy as jnp
    n, k, r = m["mamba_d_state"], m["mamba_d_conv"], m["mamba_dt_rank"]
    eps = m["rms_norm_eps"]
    t = x.shape[0]
    xin, z = jnp.split(x @ p["in_proj"], 2, axis=-1)
    xin = xin * live[:, None]                   # masked positions: zeros
    xp = jnp.concatenate([jnp.zeros((k - 1, xin.shape[1])), xin])
    xc = _silu(sum(xp[i:i + t] * p["conv_w"][i] for i in range(k))
               + p["conv_b"]) * live[:, None]
    dt_r, bm, cm = jnp.split(xc @ p["x_proj"], [r, r + n], axis=-1)
    dt_r = _rmsnorm(dt_r, p["dt_norm"]["w"], eps)
    bm = _rmsnorm(bm, p["b_norm"]["w"], eps)
    cm = _rmsnorm(cm, p["c_norm"]["w"], eps)
    dt = jax.nn.softplus(dt_r @ p["dt_proj"] + p["dt_bias"])
    y = _scan(dt, xc, bm, cm, -jnp.exp(p["A_log"]))
    return ((y + xc * p["D"]) * _silu(z)) @ p["out_proj"]


def _attention(p, x, live, m):
    import jax
    import jax.numpy as jnp
    t = x.shape[0]
    heads, kv = m["num_attention_heads"], m["num_key_value_heads"]
    hd = m["head_dim"]
    q = (x @ p["wq"]).reshape(t, heads, hd)
    k = jnp.repeat((x @ p["wk"]).reshape(t, kv, hd), heads // kv, axis=1)
    v = jnp.repeat((x @ p["wv"]).reshape(t, kv, hd), heads // kv, axis=1)
    s = jnp.einsum("qhd,khd->hqk", q, k) * hd ** -0.5
    ok = jnp.tril(jnp.ones((t, t), bool)) & (live[None, :] > 0)
    s = jnp.where(ok[None], s, -jnp.inf)
    # a masked query row sees no key: its output is garbage nobody reads
    pr = jax.nn.softmax(jnp.where(live[None, :, None] > 0, s, 0.0), axis=-1)
    o = jnp.einsum("hqk,khd->qhd", pr, v)
    return o.reshape(t, heads * hd) @ p["wo"]


def _layer(kind, p, x, live, m):
    eps = m["rms_norm_eps"]
    h = _rmsnorm(x, p["norm1"]["w"], eps)
    x = x + (_attention(p["attn"], h, live, m) if kind == "attention"
             else _mamba(p["mixer"], h, live, m))
    f = _rmsnorm(x, p["norm2"]["w"], eps)
    w = p["ffn"]
    return x + (_silu(f @ w["w1"]) * (f @ w["w3"])) @ w["w2"]


def _pick(seg, j):
    import jax
    return jax.tree.map(lambda a: jax.lax.dynamic_index_in_dim(
        a, j, keepdims=False), seg)


@functools.lru_cache(maxsize=None)
def _programs(model_key: tuple):
    """The reference's jitted programs for one model block."""
    import jax
    import jax.numpy as jnp
    m = dict(model_key)

    def forward(kind):
        return jax.jit(lambda seg, j, x, live: _layer(
            kind, _pick(seg, j), x, live, m))

    def backward(kind):
        def run(seg, j, x, live, g):
            p = _pick(seg, j)
            _, vjp = jax.vjp(lambda v: _layer(kind, p, v, live, m), x)
            return vjp(g)[0]
        return jax.jit(run)

    def head(final_w, table, x):
        v = m["vocab_size"]
        return table[:v] @ _rmsnorm(x, final_w, m["rms_norm_eps"])

    @jax.jit
    def head_grad(final_w, table, x, target):
        logits, vjp = jax.vjp(lambda v: head(final_w, table, v), x)
        return logits, vjp(jax.nn.one_hot(target, logits.shape[0]))[0]

    return ({k: forward(k) for k in ("mamba", "attention")},
            {k: backward(k) for k in ("mamba", "attention")}, head_grad,
            jax.jit(lambda table, toks: table[toks]))


def _model_key(model: dict) -> tuple:
    return tuple(sorted((k, v) for k, v in model.items()
                        if isinstance(v, (int, float, str))))


def explain_rows(params, model: dict, rows: List[np.ndarray],
                 targets: List[Optional[int]], mode: str):
    """-> per row (logits [V], target, {method: scores [S]}): each row's
    last-position logits, the target explained (its own where given, else
    the argmax) and its scores, at matmul precision ``mode``."""
    import jax
    import jax.numpy as jnp
    fwd, bwd, head_grad, embed = _programs(_model_key(model))
    layers = _layer_index(model)
    t = max(r.size for r in rows)
    t = -(-t // REF_CHUNK) * REF_CHUNK
    out = []
    with jax.default_matmul_precision(mode):
        for row, want in zip(rows, targets):
            toks = np.zeros(t, np.int32)
            toks[t - row.size:] = row
            live = jnp.asarray((np.arange(t) >= t - row.size), jnp.float32)
            e = embed(params["embed"]["table"], jnp.asarray(toks))
            xs = [e]
            for kind, seg, j in layers:
                xs.append(fwd[kind](params["segments"][seg], j, xs[-1],
                                    live))
            logits, g_last = head_grad(params["final_norm"]["w"],
                                       params["embed"]["table"], xs[-1][-1],
                                       0 if want is None else want)
            target = int(np.argmax(np.asarray(logits))) if want is None \
                else int(want)
            if want is None and target != 0:
                logits, g_last = head_grad(params["final_norm"]["w"],
                                           params["embed"]["table"],
                                           xs[-1][-1], target)
            g = jnp.zeros_like(xs[-1]).at[-1].set(g_last)
            for (kind, seg, j), x in zip(reversed(layers), reversed(xs[:-1])):
                g = bwd[kind](params["segments"][seg], j, x, live, g)
            g, e = np.asarray(g)[t - row.size:], np.asarray(e)[t - row.size:]
            out.append((np.asarray(logits, np.float32), target,
                        {"token_saliency": np.linalg.norm(g, axis=-1),
                         "token_ixg": np.sum(e * g, axis=-1)}))
            del xs, g
    return out


# -- the comparison -----------------------------------------------------------

def numbers(params, model: dict, items: List[Served]) -> Dict[str, float]:
    """Each answer against the reference at ``highest``, the reference
    explaining the target the program chose:

    * ``logit_err``: the widest logit gap of an answer over the largest
      reference logit of its prompt; the largest over the window;
    * ``target_gap``: how far below the reference's largest logit its logit
      of the served target lies, over the largest: 0 when the program chose
      the reference's argmax, a rounding-sized number on a near tie;
    * ``relevance_err.pNN``: per answer, the widest score gap over the
      reference scores' largest magnitude; the NN-th percentile.  The
      reference scores the prompt's own tokens; at the payload's padded
      positions it reads zero.
    """
    ref = explain_rows(params, model, [prompt(it.x) for it in items],
                       [it.target for it in items], "highest")
    logit_err = target_gap = 0.0
    errs = []
    for it, (lg, tgt, scores) in zip(items, ref):
        scale = max(float(np.abs(lg).max()), 1e-30)
        logit_err = max(logit_err, float(np.abs(it.logits - lg).max())
                        / scale)
        target_gap = max(target_gap, float(lg.max() - lg[tgt]) / scale)
        want = _placed(scores[it.method], it.relevance.size)
        errs.append(float(np.abs(it.relevance - want).max())
                    / max(float(np.abs(want).max()), 1e-30))
    errs.sort()
    out = {"logit_err": logit_err, "target_gap": target_gap}
    for q in RELEVANCE_PERCENTILES:
        v = percentile(errs, q)
        if v is not None:
            out[f"relevance_err.p{q}"] = v
    return out


def control_answers(params, model: dict, items: List[Served], mode: str
                    ) -> List[Served]:
    """The reference computed in ``mode``, put in the program's place: the
    same prompts answered with its own logits, argmax and scores."""
    ans = explain_rows(params, model, [prompt(it.x) for it in items],
                       [None] * len(items), mode)
    return [Served(kind=it.kind, method=it.method, x=it.x, logits=lg,
                   target=tgt,
                   relevance=_placed(scores[it.method], it.x.size))
            for it, (lg, tgt, scores) in zip(items, ans)]


# -- the work -----------------------------------------------------------------

class flops:
    """Operations and least bytes of the attribution's work, from the
    model block's published sizes and a prompt's length.  They count the
    algorithm's work, not an implementation's: a matmul is two operations
    per multiply-add, recomputation does not count, and padding does not
    count."""

    @staticmethod
    def explain(model: dict, tokens: int) -> float:
        """FP + input-gradient BP of one prompt of ``tokens`` positions.
        Every matmul's input gradient costs what its forward does; the
        head runs at the last position alone; causal attention's scores
        and weighted sum cover ``T (T + 1) / 2`` pairs per head forward and
        twice that backward."""
        d, ff, v = (model["hidden_size"], model["intermediate_size"],
                    model["vocab_size"])
        di = model["mamba_expand"] * d
        n, r = model["mamba_d_state"], model["mamba_dt_rank"]
        heads, kv, hd = (model["num_attention_heads"],
                         model["num_key_value_heads"], model["head_dim"])
        ffn = 3 * d * ff
        mamba = d * 2 * di + di * (r + 2 * n) + r * di + di * d + ffn
        attn = d * heads * hd + 2 * d * kv * hd + heads * hd * d + ffn
        kinds = _kinds(model)
        per_token = sum(attn if k == "attention" else mamba for k in kinds)
        pairs = tokens * (tokens + 1) / 2
        core = kinds.count("attention") * 2 * 2 * heads * hd * pairs
        fp = 2 * tokens * per_token + core + 2 * d * v
        return float(fp + 2 * tokens * per_token + 2 * core + 2 * d * v)

    @staticmethod
    def scan_forward(model: dict, rows: int, steps: int
                     ) -> Tuple[float, float]:
        """(operations, least bytes) of one Mamba layer's forward scan over
        ``rows`` x ``steps``: per step, channel and state, the decay's
        product and exponential, the state's multiply-add, the input term
        and the output's multiply-add (7); per step and channel ``dt x``.
        It reads dt and x and writes y ([steps, d_inner] float32 each),
        reads B and C ([steps, d_state]), A and the initial state, and
        writes the last state."""
        di = model["mamba_expand"] * model["hidden_size"]
        n = model["mamba_d_state"]
        ops = rows * steps * di * (7 * n + 1)
        nbytes = 4 * (rows * (3 * steps * di + 2 * steps * n + 2 * di * n)
                      + di * n)
        return float(ops), float(nbytes)

    @staticmethod
    def mamba_layers(model: dict) -> int:
        return _kinds(model).count("mamba")

    @staticmethod
    def request_flops(model: dict, kind: str, seeds: int, cold: bool):
        raise ValueError("a token explain's work depends on its prompt "
                         "length: lm.dispatch_mfu counts it from the plan")
