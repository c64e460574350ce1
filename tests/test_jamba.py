"""AI21-Jamba2-3B through the program's normal LM path, against the plain
reference (``tests/lm_reference/jamba.py``) on seeded random weights.

The SMOKE preset keeps one whole period of the published layer pattern
(13 Mamba layers and the attention layer at index 7) at CPU-test widths.

Tolerances.  Both sides compute in float32 on the CPU, where every dot is
a full float32 product whatever the precision asked; they differ only in
the order of sums (the program fuses and chunks, the reference runs the
plain recurrence and a whole softmax).  Measured on prompts like those
below, with the weights drawn per channel (``ref.random_params``): logits
agree within 0.5-3.9e-6 of the largest logit, scores within 0.7e-6 to
1.7e-5 of the largest score (the ``token_ixg`` sums over the hidden size
lose the most digits).  So logits are held to 1e-5 and scores to 1e-4 of
the largest.  Rounding the weights to bfloat16 alone moves the logits by
1.1e-2 to 8.5e-2 and the scores by 5e-3 to 2.9e-1, so a lower precision
than float32 fails both.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.configs as configs
from lm_reference import jamba as ref
from repro import engine as engine_lib
from repro import lm
from repro.models import transformer as tf
from repro.serve import ExplanationServer, Request

LOGIT_TOL = 1e-5
SCORE_TOL = 1e-4
MODES = {"token_saliency": "grad_norm", "token_ixg": "ixg"}


@pytest.fixture(scope="module")
def smoke():
    cfg = configs.get_smoke("jamba2-3b")
    params = ref.random_params(jax.random.PRNGKey(17),
                               tf.init(jax.random.PRNGKey(0), cfg))
    return cfg, params, ref.dims_of(cfg)


def _prompts(cfg, lengths, seed=3):
    r = np.random.default_rng(seed)
    return [r.integers(1, cfg.vocab, size=s).astype(np.int32)
            for s in lengths]


def _close(got, want, tol):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    scale = float(np.abs(want).max())
    assert float(np.abs(got - want).max()) <= tol * scale, (
        float(np.abs(got - want).max()) / scale)


def test_layer_plan_is_the_published_pattern():
    full = configs.get("jamba2-3b")
    assert full.layer_plan() == (("mamba_ffn", 7, 0), ("dense", 1, 0),
                                 ("mamba_ffn", 13, 0), ("dense", 1, 0),
                                 ("mamba_ffn", 6, 0))
    assert [i for i in range(28) if full.block_kind(i) == "dense"] == [7, 21]
    assert full.with_(n_layers=14).layer_plan() == (
        ("mamba_ffn", 7, 0), ("dense", 1, 0), ("mamba_ffn", 6, 0))
    assert configs.get_smoke("jamba2-3b").layer_plan() == (
        ("mamba_ffn", 7, 0), ("dense", 1, 0), ("mamba_ffn", 6, 0))


def _published_count(layers: int) -> int:
    """Parameters from the published config, term by term."""
    d, di, n, dtr, conv, ff, v = 2560, 5120, 16, 160, 4, 8192, 65536
    heads, kv, hd = 20, 1, 128
    mixer = (d * 2 * di                 # in_proj (no bias)
             + conv * di + di           # depthwise conv and its bias
             + di * (dtr + 2 * n)       # x_proj
             + dtr * di + di            # dt_proj and its bias
             + di * n + di              # A_log, D
             + di * d                   # out_proj
             + dtr + 2 * n)             # dt/B/C RMSNorm weights
    attn = d * heads * hd + 2 * d * kv * hd + heads * hd * d
    ffn = 3 * d * ff
    n_attn = sum(i % 14 == 7 for i in range(layers))
    return ((layers - n_attn) * (mixer + ffn + 2 * d)
            + n_attn * (attn + ffn + 2 * d) + v * d + d)


def test_param_count_is_the_published_one():
    full = configs.get("jamba2-3b")
    assert _published_count(28) == 3_029_337_472
    assert full.param_count() == 3_029_337_472
    assert _published_count(14) == 1_598_556_096
    assert full.with_(n_layers=14).param_count() == 1_598_556_096
    shapes = jax.eval_shape(lambda: tf.init(jax.random.PRNGKey(0), full))
    assert sum(a.size for a in jax.tree.leaves(shapes)) == 3_029_337_472


def test_smoke_param_tree_holds_what_param_count_counts(smoke):
    cfg, params, _ = smoke
    assert sum(a.size for a in jax.tree.leaves(params)) == cfg.param_count()


@pytest.mark.parametrize("method", sorted(MODES))
def test_engine_explain_tokens_matches_the_reference(smoke, method):
    cfg, params, dims = smoke
    eng = engine_lib.build(engine_lib.EngineSpec(
        model=engine_lib.LMModel(params, cfg), precision="f32"))
    prompts = np.stack(_prompts(cfg, [12, 12], seed=5))
    logits, scores = eng.explain_tokens({"tokens": jnp.asarray(prompts)},
                                        mode=MODES[method])
    for row in range(len(prompts)):
        want_lg, want = ref.explain(params, dims, prompts[row])
        _close(logits[row], want_lg, LOGIT_TOL)
        _close(scores[row], want[method], SCORE_TOL)


def test_served_answers_match_the_reference_across_length_buckets(smoke):
    """Left-padded prompts of two pow2 buckets, both methods, submitted
    together: equal-length prompts share a launch, the buckets never do,
    and every answer is the reference's on the prompt without its
    padding, with zero scores at the padded positions."""
    cfg, params, dims = smoke
    adapter = lm.LMAdapter(params, cfg, precision="f32", max_batch=4)
    server = ExplanationServer(adapter, max_delay_s=0.0)
    assert server.batcher.fill_target == 4
    tokens = _prompts(cfg, [5, 7, 8, 11, 13, 16])
    prompts = [np.asarray(lm.pad_tokens(t)) for t in tokens]
    assert sorted({p.shape[0] for p in prompts}) == [8, 16]
    reqs = [Request(uid=f"p{i}.{m}", kind="explain", x=p, method=m)
            for i, p in enumerate(prompts) for m in sorted(MODES)]
    for r in reqs:
        server.submit(r)
    out = {r.uid: r for r in server.drain()}
    assert len(out) == len(reqs) and all(r.ok for r in out.values())
    assert max(r.batch_size for r in out.values()) == 4   # co-batched
    for i, (t, p) in enumerate(zip(tokens, prompts)):
        want_lg, want = ref.explain(params, dims, t)
        for m in MODES:
            resp = out[f"p{i}.{m}"]
            _close(resp.logits, want_lg, LOGIT_TOL)
            rel = np.asarray(resp.relevance)
            _close(rel[p.size - t.size:], want[m], SCORE_TOL)
            assert not rel[:p.size - t.size].any()
            assert resp.targets == (int(np.argmax(want_lg)),)


@pytest.mark.parametrize("length,chunked", [(8, False), (13, False),
                                            (13, True)])
def test_left_padding_changes_no_live_position(smoke, length, chunked):
    """A prompt padded to a longer bucket explains as the prompt alone,
    with whole-softmax attention and with chunked (online-softmax)
    attention over 8-key chunks: the padding reaches no mixer, and its
    positions score zero."""
    cfg, params, _ = smoke
    if chunked:
        cfg = cfg.with_(attn_chunk=8, attn_chunk_threshold=16)
    eng = engine_lib.build(engine_lib.EngineSpec(
        model=engine_lib.LMModel(params, cfg), precision="f32"))
    t, = _prompts(cfg, [length], seed=11)
    padded = np.asarray(lm.pad_tokens(t, 32))
    for mode in ("grad_norm", "ixg"):
        lg, sc = eng.explain_tokens({"tokens": jnp.asarray(t[None])},
                                    mode=mode)
        lg_p, sc_p = eng.explain_tokens({"tokens": jnp.asarray(padded[None])},
                                        mode=mode)
        _close(lg_p[0], lg[0], LOGIT_TOL)
        _close(np.asarray(sc_p[0])[32 - length:], sc[0], SCORE_TOL)
        assert not np.asarray(sc_p[0])[:32 - length].any()


def test_served_token_launches_count_live_and_padded_positions(smoke):
    from repro.obs.trace import Tracer
    cfg, params, _ = smoke
    server = ExplanationServer(lm.LMAdapter(params, cfg, max_batch=4),
                               max_delay_s=0.0, tracer=Tracer())
    lengths = [5, 7, 13]
    for i, t in enumerate(_prompts(cfg, lengths)):
        server.submit(Request(uid=f"q{i}", kind="explain",
                              x=np.asarray(lm.pad_tokens(t)),
                              method="token_ixg"))
    assert all(r.ok for r in server.drain())
    spans = [s for s in server.tracer.spans if s.name == "engine.attribute"]
    got = sorted((s.args["rows"], s.args["tokens_live"],
                  s.args["tokens_padded"]) for s in spans)
    assert got == [(1, 13, 16), (2, 12, 16)]
