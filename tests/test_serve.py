"""repro.serve subsystem: registry dispatch parity, micro-batcher
round-trips, residual-cache hit path, and the end-to-end server loop."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import attribution
from repro.models import cnn
from repro.serve import (CNNAdapter, ExplanationServer, MicroBatcher,
                         Request, ResidualCache, bucket_key, registry,
                         residual_bits)
from repro.obs.trace import Tracer
from repro.serve import residual_cache
from repro.serve.api import EXPLAIN, PREDICT

CFG = cnn.CNNConfig(in_hw=(8, 8), channels=(4, 4), fc=(16,))


@pytest.fixture(scope="module")
def setup():
    params = cnn.init(jax.random.PRNGKey(0), CFG)
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 8, 8, 3))
    return params, CNNAdapter(params, CFG), x


def make_server(adapter, **kw):
    kw.setdefault("max_batch", 4)
    kw.setdefault("max_delay_s", 0.0)
    return ExplanationServer(adapter, **kw)


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------


def test_registry_lists_every_method():
    names = registry.names()
    for m in ("saliency", "deconvnet", "guided", "input_x_gradient",
              "integrated_gradients", "smoothgrad", "token_saliency",
              "token_ixg", "token_contrastive"):
        assert m in names
    assert set(registry.mask_reuse_methods()) == {
        "saliency", "deconvnet", "guided"}
    assert set(registry.token_methods()) == {
        "saliency", "deconvnet", "guided",
        "token_saliency", "token_ixg", "token_contrastive"}
    with pytest.raises(KeyError):
        registry.get("no_such_method")


@pytest.mark.parametrize("method", ["saliency", "deconvnet", "guided"])
def test_registry_pure_bp_parity(setup, method):
    """Registry dispatch is bit-exact with the direct core call."""
    params, adapter, x = setup
    f = adapter.model_fn(method)
    expl = registry.make(method, f)
    logits_r, rel_r = expl.attribute(x)
    logits_d, rel_d = attribution.attribute(f, x)
    np.testing.assert_array_equal(np.asarray(rel_r), np.asarray(rel_d))
    np.testing.assert_array_equal(np.asarray(logits_r), np.asarray(logits_d))


def test_registry_composite_parity(setup):
    params, adapter, x = setup
    f = adapter.model_fn("saliency")
    _, ig_r = registry.make("integrated_gradients", f, steps=4).attribute(x)
    _, ig_d = attribution.integrated_gradients(f, x, steps=4)
    np.testing.assert_array_equal(np.asarray(ig_r), np.asarray(ig_d))

    key = jax.random.PRNGKey(3)
    _, sg_r = registry.make("smoothgrad", f, n=3).attribute(x, key=key)
    _, sg_d = attribution.smoothgrad(f, x, key, n=3)
    np.testing.assert_array_equal(np.asarray(sg_r), np.asarray(sg_d))

    _, ixg_r = registry.make("input_x_gradient", f).attribute(x)
    _, ixg_d = attribution.input_x_gradient(f, x)
    np.testing.assert_array_equal(np.asarray(ixg_r), np.asarray(ixg_d))


def test_registry_rejects_duplicates():
    with pytest.raises(ValueError):
        @registry.register("saliency")
        class Dup(registry.Explainer):
            pass


# ---------------------------------------------------------------------------
# batched IG / SmoothGrad (the lax.map replacement)
# ---------------------------------------------------------------------------


def test_integrated_gradients_batched_equals_sequential(setup):
    params, adapter, x = setup
    f = lambda v: cnn.apply(params, v, CFG, method="saliency")
    _, b = attribution.integrated_gradients(f, x, steps=4)
    _, s = attribution.integrated_gradients(f, x, steps=4, batched=False)
    np.testing.assert_allclose(np.asarray(b), np.asarray(s), atol=1e-6)


def test_smoothgrad_batched_equals_sequential(setup):
    params, adapter, x = setup
    f = lambda v: cnn.apply(params, v, CFG, method="saliency")
    key = jax.random.PRNGKey(7)
    _, b = attribution.smoothgrad(f, x, key, n=3)
    _, s = attribution.smoothgrad(f, x, key, n=3, batched=False)
    np.testing.assert_allclose(np.asarray(b), np.asarray(s), atol=1e-6)


def test_integrated_gradients_batched_pytree(setup):
    """The fold helper handles pytree inputs (VLM-style dict leaves)."""
    params, adapter, x = setup
    g = lambda d: cnn.apply(params, d["img"], CFG, method="saliency")
    _, b = attribution.integrated_gradients(g, {"img": x}, steps=4)
    _, s = attribution.integrated_gradients(g, {"img": x}, steps=4,
                                            batched=False)
    np.testing.assert_allclose(np.asarray(b["img"]), np.asarray(s["img"]),
                               atol=1e-6)


# ---------------------------------------------------------------------------
# micro-batcher
# ---------------------------------------------------------------------------


def test_bucket_key_separates_incompatible_requests():
    a = Request(uid="a", kind=EXPLAIN, x=np.zeros((8, 8, 3), np.float32))
    b = Request(uid="b", kind=EXPLAIN, x=np.zeros((8, 8, 3), np.float32))
    assert bucket_key(a) == bucket_key(b)
    for other in [
            Request(uid="c", kind=PREDICT, x=np.zeros((8, 8, 3), np.float32)),
            Request(uid="c", kind=EXPLAIN, x=np.zeros((4, 4, 3), np.float32)),
            Request(uid="c", kind=EXPLAIN, x=np.zeros((8, 8, 3), np.float32),
                    method="guided"),
            Request(uid="c", kind=EXPLAIN, x=np.zeros((8, 8, 3), np.float32),
                    topk=3),
            Request(uid="c", kind=EXPLAIN, x=np.zeros((8, 8, 3), np.float32),
                    target=1),
    ]:
        assert bucket_key(other) != bucket_key(a)
    # key-folding stochastic methods CO-BATCH: each request rides its own
    # PRNG key (folded along the batch axis), so sharing a launch is safe
    s1 = Request(uid="s1", kind=EXPLAIN, x=np.zeros((8, 8, 3), np.float32),
                 method="smoothgrad")
    s2 = Request(uid="s2", kind=EXPLAIN, x=np.zeros((8, 8, 3), np.float32),
                 method="smoothgrad")
    assert bucket_key(s1) == bucket_key(s2)
    assert s1.batch_token is None       # no singleton token was minted


def test_non_foldable_stochastic_methods_stay_singleton():
    """A stochastic explainer WITHOUT key folding still gets per-request
    singleton buckets (the pre-fold dispatch could only use one key)."""
    @registry.register("_test_nofold")
    class NoFold(registry.Explainer):
        needs_key = True
        fold_keys = False
    try:
        s1 = Request(uid="s1", kind=EXPLAIN,
                     x=np.zeros((8, 8, 3), np.float32), method="_test_nofold")
        s2 = Request(uid="s2", kind=EXPLAIN,
                     x=np.zeros((8, 8, 3), np.float32), method="_test_nofold")
        assert bucket_key(s1) != bucket_key(s2)
        assert isinstance(s1.batch_token, int)
    finally:
        registry._REGISTRY.pop("_test_nofold")


def test_batcher_deadline_and_fill():
    t = [0.0]
    mb = MicroBatcher(max_batch=2, max_delay_s=1.0, clock=lambda: t[0])
    mk = lambda u: Request(uid=u, kind=PREDICT,
                           x=np.zeros((4, 4, 3), np.float32))
    mb.submit(mk("a"))
    assert mb.ready() == []                     # neither full nor expired
    mb.submit(mk("b"))
    full = mb.ready()
    assert len(full) == 1 and len(full[0].requests) == 2   # popped on fill
    mb.submit(mk("c"))
    assert mb.ready() == []
    t[0] = 2.0
    expired = mb.ready()
    assert len(expired) == 1 and expired[0].requests[0].uid == "c"
    assert mb.pending() == 0


def test_batcher_padding_roundtrip(setup):
    """Requests served through padded batches == served one at a time."""
    params, adapter, x = setup
    # batch of 3 -> padded to 4; per-example results must be unchanged
    srv_b = make_server(adapter)
    for i in range(3):      # submit-then-drain so the bucket coalesces
        srv_b.submit(Request(uid=f"r{i}", kind=EXPLAIN, x=x[i],
                             method="saliency"))
    out_b = {r.uid: r for r in srv_b.drain()}
    assert {r.batch_size for r in out_b.values()} == {4}   # pow2-padded
    for i in range(3):
        srv_1 = make_server(adapter, max_batch=1)
        out_1 = srv_1.serve([Request(uid=f"r{i}", kind=EXPLAIN, x=x[i],
                                     method="saliency")])
        np.testing.assert_array_equal(
            np.asarray(out_b[f"r{i}"].relevance),
            np.asarray(out_1[f"r{i}"].relevance))


# ---------------------------------------------------------------------------
# residual cache
# ---------------------------------------------------------------------------


def test_cache_lru_eviction_and_accounting():
    cache = ResidualCache(capacity=2)
    put = lambda uid: cache.store([uid], np.zeros((1, 10)),
                                  {"m": np.zeros((1, 4), np.uint8)},
                                  "saliency")
    put("a")
    put("b")
    assert cache.get("a") is not None           # refreshes recency
    put("c")                                    # evicts b (LRU)
    assert "b" not in cache and "a" in cache and "c" in cache
    assert cache.get("b") is None
    st = cache.stats
    assert (st.hits, st.misses, st.evictions) == (1, 1, 1)
    assert st.bits_stored == 2 * 4 * 8
    assert residual_bits({"m": np.zeros((1, 4), np.uint8)}) == 32


def test_cache_entry_bits_match_paper_scale(setup):
    """Cached residuals are mask-sized (Kb), not activation-sized (Mb)."""
    params, adapter, x = setup
    logits, residuals = adapter.predict(x[:1])
    bits = residual_bits(residuals)
    act_bits = 32 * sum(np.prod(s) for s in
                        [(8, 8, 4), (8, 8, 4), (4, 4, 4), (16,)])
    assert bits < act_bits / 10     # >10x smaller than caching activations


def test_explain_after_predict_hits_and_skips_forward(setup):
    """The tentpole behavior: explain-after-predict = BP phase only,
    bit-exact with the cold (FP+BP) path."""
    params, adapter, x = setup
    cold_srv = make_server(adapter)
    cold = cold_srv.serve([Request(uid="a", kind=EXPLAIN, x=x[0],
                                   method="guided")])["a"]
    assert not cold.cache_hit

    hot_srv = make_server(adapter)
    out = hot_srv.serve([Request(uid="a", kind=PREDICT, x=x[0]),
                         Request(uid="a", kind=EXPLAIN, x=x[0],
                                 method="guided")])
    hot = out["a"]
    assert hot.cache_hit and hot.kind == EXPLAIN
    np.testing.assert_array_equal(np.asarray(hot.relevance),
                                  np.asarray(cold.relevance))
    np.testing.assert_array_equal(np.asarray(hot.logits),
                                  np.asarray(cold.logits))
    assert hot_srv.cache.stats.hits == 1


@pytest.mark.parametrize("method", ["saliency", "deconvnet", "guided"])
def test_one_predict_serves_every_bp_method(setup, method):
    """Masks stored once at predict time serve ANY pure-BP method's
    backward (deconvnet reads only the gradient sign, guided ANDs the
    mask in) — the paper's store-once / explain-many amortization."""
    params, adapter, x = setup
    srv = make_server(adapter)
    out = srv.serve([Request(uid="a", kind=PREDICT, x=x[1]),
                     Request(uid="a", kind=EXPLAIN, x=x[1], method=method)])
    assert out["a"].cache_hit
    f = adapter.model_fn(method)
    _, rel = attribution.attribute(f, x[1:2])
    np.testing.assert_allclose(np.asarray(out["a"].relevance),
                               np.asarray(rel[0]), atol=1e-6)


def test_topk_panel_matches_attribute_classes(setup):
    """K-class panel rides the seed axis; equals the seed-batched engine."""
    params, adapter, x = setup
    srv = make_server(adapter)
    out = srv.serve([Request(uid="a", kind=PREDICT, x=x[2]),
                     Request(uid="a", kind=EXPLAIN, x=x[2],
                             method="saliency", topk=3)])
    resp = out["a"]
    assert resp.cache_hit and len(resp.targets) == 3
    assert resp.relevance.shape == (3, 8, 8, 3)
    fwd, bwd = cnn.seed_batched_attribution(params, CFG, "saliency")
    _, panel = attribution.attribute_classes(
        fwd, x[2:3], jnp.asarray(resp.targets), backward=bwd)
    np.testing.assert_allclose(np.asarray(resp.relevance),
                               np.asarray(panel[:, 0]), atol=1e-6)
    # targets really are the top-3 of the predicted logits
    top3 = np.argsort(-np.asarray(resp.logits))[:3]
    assert list(resp.targets) == top3.tolist()


def test_lru_eviction_forces_cold_path(setup):
    params, adapter, x = setup
    srv = make_server(adapter, cache_capacity=1)
    out = srv.serve([Request(uid="a", kind=PREDICT, x=x[0]),
                     Request(uid="b", kind=PREDICT, x=x[1]),
                     Request(uid="a", kind=EXPLAIN, x=x[0],
                             method="saliency")])
    assert not out["a"].cache_hit               # evicted by b's predict
    # 2 evictions: b's predict evicts a, then a's cold-explain warm evicts b
    assert srv.cache.stats.evictions == 2
    assert srv.cache.stats.misses == 1


# ---------------------------------------------------------------------------
# server loop
# ---------------------------------------------------------------------------


def test_mixed_workload_end_to_end(setup):
    params, adapter, x = setup
    srv = make_server(adapter, max_batch=2)
    reqs = [Request(uid=f"p{i}", kind=PREDICT, x=x[i]) for i in range(4)]
    reqs += [Request(uid=f"p{i}", kind=EXPLAIN, x=x[i], method="guided")
             for i in range(4)]
    reqs.append(Request(uid="x0", kind=EXPLAIN, x=x[0],
                        method="integrated_gradients"))
    reqs.append(Request(uid="x1", kind=EXPLAIN, x=x[1], method="smoothgrad",
                        key=jax.random.PRNGKey(5)))
    out = srv.serve(reqs)
    assert len(out) == 6                        # 4 ids + x0 + x1
    assert all(out[f"p{i}"].cache_hit for i in range(4))
    assert not out["x0"].cache_hit and not out["x1"].cache_hit
    snap = srv.stats.snapshot()
    assert snap["requests"] == len(reqs)
    assert snap["methods"]["explain/guided"]["hit_rate"] == 1.0
    assert snap["methods"]["predict"]["count"] == 4
    assert srv.cache.stats.hit_rate() == 1.0    # every reusable explain hit


def test_explain_with_explicit_target(setup):
    params, adapter, x = setup
    srv = make_server(adapter)
    out = srv.serve([Request(uid="a", kind=PREDICT, x=x[0]),
                     Request(uid="a", kind=EXPLAIN, x=x[0],
                             method="saliency", target=7)])
    assert out["a"].targets == (7,)
    f = adapter.model_fn("saliency")
    _, rel = attribution.attribute(f, x[0:1], target=jnp.asarray([7]))
    np.testing.assert_allclose(np.asarray(out["a"].relevance),
                               np.asarray(rel[0]), atol=1e-6)


def test_server_rejects_bad_requests(setup):
    params, adapter, x = setup
    srv = make_server(adapter)
    with pytest.raises(KeyError):
        srv.submit(Request(uid="a", kind=EXPLAIN, x=x[0], method="nope"))
    with pytest.raises(ValueError):
        srv.submit(Request(uid="a", kind=EXPLAIN, x=x[0],
                           method="integrated_gradients", topk=3))
    with pytest.raises(ValueError):
        Request(uid="a", kind="unknown", x=x[0])
    with pytest.raises(ValueError):
        Request(uid="a", kind=PREDICT, x=x[0], topk=3)


def test_smoothgrad_cobatched_requests_keep_their_own_keys(setup):
    """Regression for the first-key dispatch bug: two CO-BATCHED stochastic
    requests with distinct PRNG keys share one launch (per-request keys
    folded along the batch axis) yet each gets a DIFFERENT heatmap that is
    bitwise identical to serving it alone with its own key."""
    params, adapter, x = setup
    srv = make_server(adapter)
    k1, k2 = jax.random.PRNGKey(1), jax.random.PRNGKey(2)
    srv.submit(Request(uid="u", kind=EXPLAIN, x=x[0], method="smoothgrad",
                       key=k1))
    srv.submit(Request(uid="u", kind=EXPLAIN, x=x[0], method="smoothgrad",
                       key=k2))
    out = srv.drain()
    assert len(out) == 2 and {r.batch_size for r in out} == {2}
    # same input, different keys -> different draws, different heatmaps
    assert not np.array_equal(np.asarray(out[0].relevance),
                              np.asarray(out[1].relevance))
    # ...and each is per-key deterministic: identical to singleton serving
    f = adapter.model_fn("saliency")
    for resp, key in zip(out, [k1, k2]):
        _, sg = attribution.smoothgrad(f, x[0:1], key)
        np.testing.assert_array_equal(np.asarray(resp.relevance),
                                      np.asarray(sg[0]))


def test_deconvnet_stored_masks_only_replay_deconvnet(setup):
    """An adapter storing under deconvnet rules keeps NO ReLU masks; a
    guided explain must fall back to the cold path, not crash mid-serve."""
    params, adapter, x = setup
    adp = type(adapter)(params, CFG, store_rules="deconvnet")
    srv = make_server(adp)
    out = srv.serve([Request(uid="a", kind=PREDICT, x=x[0]),
                     Request(uid="a", kind=EXPLAIN, x=x[0], method="guided"),
                     Request(uid="a", kind=EXPLAIN, x=x[0],
                             method="deconvnet")])
    # dict keeps the last response per uid (deconvnet) — check via stats
    snap = srv.stats.snapshot()["methods"]
    assert snap["explain/guided"]["hit_rate"] == 0.0      # unusable masks
    assert snap["explain/deconvnet"]["hit_rate"] == 1.0   # compatible
    assert srv.cache.stats.misses == 1
    assert out["a"].method == "deconvnet"
    # and the cold guided result equals the direct engine call
    f = adp.model_fn("guided")
    _, rel = attribution.attribute(f, x[0:1])
    cold = srv.serve([Request(uid="g", kind=EXPLAIN, x=x[0],
                              method="guided")])["g"]
    np.testing.assert_array_equal(np.asarray(cold.relevance),
                                  np.asarray(rel[0]))


class _Recording:
    """Adapter proxy that keeps every predict's (logits, residuals)."""

    def __init__(self, inner):
        self.inner, self.outputs = inner, []

    def predict(self, xb):
        out = self.inner.predict(xb)
        self.outputs.append(out)
        return out

    def __getattr__(self, name):
        return getattr(self.inner, name)


def _predict_launch(srv, x, uids):
    """One predict launch of ``len(uids)`` rows (submit, then drain)."""
    for i, uid in enumerate(uids):
        srv.submit(Request(uid=uid, kind=PREDICT, x=x[i]))
    return {r.uid: r for r in srv.drain()}


def _explain_group(srv, x, rows, method="guided", prefix="p"):
    """Explains of ``rows`` (uids ``<prefix><row>``) in ONE launch."""
    for i in rows:
        srv.submit(Request(uid=f"{prefix}{i}", kind=EXPLAIN, x=x[i],
                           method=method))
    return {r.uid: r for r in srv.drain()}


@pytest.fixture(params=["f32", "fxp16"])
def served(request):
    return request.getfixturevalue(
        "setup" if request.param == "f32" else "setup_fxp")


def test_multi_row_predict_hits_bitwise_equal_cold(served):
    """Hits of every row of a 4-row predict, each alone and all four in
    one group, equal a cold explain of the same images bitwise."""
    _, adapter, x = served
    cold = _explain_group(make_server(adapter), x, range(4))
    assert not any(r.cache_hit for r in cold.values())
    singles = {}
    for i in range(4):
        singles.update(_explain_group(make_server(adapter), x, [i],
                                      prefix="s"))
    srv = make_server(adapter)
    _predict_launch(srv, x, [f"p{i}" for i in range(4)])
    assert [srv.cache.peek(f"p{i}").slot for i in range(4)] == [0, 1, 2, 3]
    alone = {}
    for i in (3, 1, 2, 0):
        alone.update(_explain_group(srv, x, [i]))
    group = _explain_group(srv, x, range(4))
    for i in range(4):
        for hit in (alone[f"p{i}"], group[f"p{i}"]):
            assert hit.cache_hit
            np.testing.assert_array_equal(np.asarray(hit.relevance),
                                          np.asarray(cold[f"p{i}"].relevance))
            np.testing.assert_array_equal(np.asarray(hit.logits),
                                          np.asarray(cold[f"p{i}"].logits))
            np.testing.assert_array_equal(
                np.asarray(hit.relevance),
                np.asarray(singles[f"s{i}"].relevance))
        assert alone[f"p{i}"].batch_size == 1 and group[f"p{i}"].batch_size == 4


def test_entry_bits_are_one_examples(served):
    """Entries account ONE example's bits, whatever launch stored them, so
    ``bits_stored`` and ``peak_bits`` read as one row per entry."""
    _, adapter, x = served
    rec = _Recording(adapter)
    srv = make_server(rec)
    _predict_launch(srv, x, [f"p{i}" for i in range(3)])    # 4 rows, 3 live
    srv.serve([Request(uid="one", kind=PREDICT, x=x[3])])
    (logits4, res4), (_, res1) = rec.outputs
    per_example = residual_bits(res4) // 4
    assert per_example == residual_bits(res1) > 0
    for i in range(3):
        entry = srv.cache.peek(f"p{i}")
        assert entry.bits == per_example
        assert residual_bits(srv.cache.gather([entry], 1)) == per_example
    assert srv.cache.peek("one").bits == per_example
    st = srv.cache.stats
    assert st.bits_stored == st.peak_bits == 4 * per_example
    cache = ResidualCache(capacity=2)        # the third row evicts p0, and
    cache.store([f"p{i}" for i in range(3)], np.asarray(logits4), res4,
                "saliency")                  # its bits leave with it
    assert cache.stats.bits_stored == 2 * per_example
    assert cache.stats.peak_bits == 3 * per_example   # read before evicting


_MORE = jax.random.normal(jax.random.PRNGKey(7), (7, 8, 8, 3))


def test_hit_group_from_three_launches_equals_cold(served):
    """One hit group drawn from predict launches of 1, 2 and 4 rows equals
    a cold explain of the same images bitwise."""
    _, adapter, _ = served
    x = _MORE
    srv = make_server(adapter)
    _predict_launch(srv, x[:1], ["q0"])
    _predict_launch(srv, x[1:3], ["q1", "q2"])
    _predict_launch(srv, x[3:7], ["q3", "q4", "q5", "q6"])
    rows = [6, 0, 2, 4]
    group = _explain_group(srv, x, rows, prefix="q")
    cold = _explain_group(make_server(adapter), x, rows, prefix="c")
    assert srv.cache.stats.gathers == 1
    for i in rows:
        hit = group[f"q{i}"]
        assert hit.cache_hit and hit.batch_size == 4
        np.testing.assert_array_equal(np.asarray(hit.relevance),
                                      np.asarray(cold[f"c{i}"].relevance))
        np.testing.assert_array_equal(np.asarray(hit.logits),
                                      np.asarray(cold[f"c{i}"].logits))


def test_evicted_slot_is_reused_without_stale_rows(served, monkeypatch):
    """A slot freed by eviction goes to the next stored example, and hits
    read that example's row: across launches, and within one launch whose
    later rows evict its earlier ones (no write names a slot twice)."""
    _, adapter, _ = served
    x = _MORE
    writes = []
    write = residual_cache._write

    def recording(mesh, table, slots, rows):
        writes.append(slots.tolist())
        return write(mesh, table, slots, rows)

    monkeypatch.setattr(residual_cache, "_write", recording)
    cold = _explain_group(make_server(adapter), x, range(6), prefix="c")
    srv = make_server(adapter, cache_capacity=2)
    _predict_launch(srv, x[:2], ["q0", "q1"])
    slot0 = srv.cache.peek("q0").slot
    _predict_launch(srv, x[2:3], ["q2"])                  # evicts q0
    assert "q0" not in srv.cache and srv.cache.peek("q2").slot == slot0
    got = _explain_group(srv, x, [2, 1], prefix="q")
    # 3 live rows and a padding row: q3 is stored, then evicted by q5
    _predict_launch(srv, x[3:6], ["q3", "q4", "q5"])
    assert len(srv.cache) == 2 and "q4" in srv.cache and "q5" in srv.cache
    assert {srv.cache.peek(u).slot for u in ("q4", "q5")} == {0, 1}
    assert writes[-1] == [2, srv.cache.peek("q4").slot,
                          srv.cache.peek("q5").slot, 2]      # 2: dropped
    got.update(_explain_group(srv, x, [5, 4], prefix="q"))
    for i in (2, 1, 5, 4):
        assert got[f"q{i}"].cache_hit
        np.testing.assert_array_equal(np.asarray(got[f"q{i}"].relevance),
                                      np.asarray(cold[f"c{i}"].relevance))


def test_rows_per_gather_counts_each_groups_take(served):
    """Each hit group is one take of its padded size: the counter and the
    ``cache.gather`` span's ``rows`` read the same sequence."""
    _, adapter, x = served
    tracer = Tracer()
    srv = make_server(adapter, tracer=tracer)
    srv.serve([Request(uid="z", kind=PREDICT, x=x[0])])
    srv.serve([Request(uid="z", kind=EXPLAIN, x=x[0], method="saliency")])
    _predict_launch(srv, x, ["p0", "p1", "p2"])
    _explain_group(srv, x, [1], method="saliency")
    _explain_group(srv, x, [0, 1, 2], method="saliency")      # padded to 4
    _explain_group(srv, x, [0, 1], method="saliency")
    st = srv.cache.stats
    assert (st.gathers, st.gathered_rows) == (4, 8)
    assert st.snapshot()["rows_per_gather"] == 2.0
    assert [s.args["rows"] for s in tracer.spans
            if s.name == "cache.gather"] == [1, 1, 4, 2]
    assert [s.name for s in tracer.spans].count("cache.store") == 2


class _Lowerings:
    """Counts the programs JAX lowers while ``on`` (a jit cache miss)."""

    _EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
    _active = None

    def __init__(self):
        self.on, self.names = False, []
        if _Lowerings._active is None:
            jax.monitoring.register_event_duration_secs_listener(
                lambda event, _s, **kw: _Lowerings._active._event(event, kw))
        _Lowerings._active = self

    def _event(self, event, kw):
        if self.on and event == self._EVENT:
            self.names.append(str(kw.get("fun_name", "?")))


def test_group_across_launches_lowers_no_new_program(served):
    """Once one launch of each padded size has been stored and gathered,
    groups drawn from several launches run programs already lowered."""
    _, adapter, _ = served
    x = _MORE
    warm = make_server(adapter)
    for n in (1, 2, 4):
        uids = [f"w{n}.{i}" for i in range(n)]
        _predict_launch(warm, x, uids)
        _explain_group(warm, x, range(n), prefix=f"w{n}.")
    low = _Lowerings()
    srv = make_server(adapter)
    low.on = True
    _predict_launch(srv, x[:1], ["q0"])
    _predict_launch(srv, x[1:3], ["q1", "q2"])
    _predict_launch(srv, x[3:7], ["q3", "q4", "q5", "q6"])
    out = _explain_group(srv, x, [6, 0, 2], prefix="q")
    out.update(_explain_group(srv, x, [1, 5], prefix="q"))
    low.on = False
    assert all(r.cache_hit for r in out.values()) and len(out) == 5
    assert srv.cache.stats.gathers == 2
    assert low.names == []


def test_cold_bp_explain_warms_cache(setup):
    """A cold pure-BP explain stores its forward's masks: the next explain
    for the same uid (any BP method) skips the forward."""
    params, adapter, x = setup
    srv = make_server(adapter)
    first = srv.serve([Request(uid="w", kind=EXPLAIN, x=x[3],
                               method="saliency")])["w"]
    second = srv.serve([Request(uid="w", kind=EXPLAIN, x=x[3],
                                method="deconvnet")])["w"]
    assert not first.cache_hit and second.cache_hit


# ---------------------------------------------------------------------------
# true int16 fixed-point serving (precision="fxp16")
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def setup_fxp(setup):
    params, _, x = setup
    return params, CNNAdapter(params, CFG, precision="fxp16"), x


def test_fxp_predict_explain_hit_skips_forward(setup_fxp):
    """The quantized path keeps the serving contract: explain-after-predict
    is a cache hit, and hit == cold bitwise (same two int16 programs)."""
    _, adapter, x = setup_fxp
    srv = make_server(adapter)
    srv.serve([Request(uid="q0", kind=PREDICT, x=x[0])])
    hit = srv.serve([Request(uid="q0", kind=EXPLAIN, x=x[0],
                             method="guided")])["q0"]
    assert hit.cache_hit
    cold = srv.serve([Request(uid="q1", kind=EXPLAIN, x=x[0],
                              method="guided")])["q1"]
    assert not cold.cache_hit
    np.testing.assert_array_equal(np.asarray(hit.relevance),
                                  np.asarray(cold.relevance))
    assert hit.relevance.dtype == jnp.float32      # dequantized at the edge


def test_fxp_composite_methods_run_via_manual_engine(setup_fxp):
    """IG / smoothgrad / input-x-gradient run quantized end-to-end through
    the registry's manual ``backward`` (no jax.vjp of integers)."""
    _, adapter, x = setup_fxp
    srv = make_server(adapter)
    out = srv.serve([
        Request(uid="ig", kind=EXPLAIN, x=x[1],
                method="integrated_gradients"),
        Request(uid="sg", kind=EXPLAIN, x=x[1], method="smoothgrad",
                key=jax.random.PRNGKey(7)),
        Request(uid="ixg", kind=EXPLAIN, x=x[1],
                method="input_x_gradient"),
    ])
    for uid in ("ig", "sg", "ixg"):
        rel = np.asarray(out[uid].relevance)
        assert rel.shape == (8, 8, 3) and np.isfinite(rel).all()
        assert np.abs(rel).sum() > 0


def test_fxp_topk_panel_rides_seed_axis(setup_fxp):
    _, adapter, x = setup_fxp
    srv = make_server(adapter)
    srv.serve([Request(uid="t", kind=PREDICT, x=x[2])])
    resp = srv.serve([Request(uid="t", kind=EXPLAIN, x=x[2],
                              method="saliency", topk=3)])["t"]
    assert resp.cache_hit and resp.relevance.shape == (3, 8, 8, 3)
    assert len(resp.targets) == 3


def test_fxp_relevance_tracks_f32_ranks(setup, setup_fxp):
    """Serving-level fidelity: the quantized saliency map rank-correlates
    with the float one (the core bar is asserted in test_fidelity.py)."""
    from repro.core import fidelity
    _, adapter_f, x = setup
    _, adapter_q, _ = setup_fxp
    rf = make_server(adapter_f).serve(
        [Request(uid="a", kind=EXPLAIN, x=x[0], method="saliency")])["a"]
    rq = make_server(adapter_q).serve(
        [Request(uid="a", kind=EXPLAIN, x=x[0], method="saliency")])["a"]
    hm_f = attribution.heatmap(rf.relevance[None])[0]
    hm_q = attribution.heatmap(rq.relevance[None])[0]
    assert fidelity.spearman(np.asarray(hm_f), np.asarray(hm_q)) > 0.8


def test_adapter_rejects_unknown_precision(setup):
    params, _, _ = setup
    with pytest.raises(ValueError):
        CNNAdapter(params, CFG, precision="int4")


# ---------------------------------------------------------------------------
# hardening: malformed requests, fault isolation, typed sheds (real adapter)
# ---------------------------------------------------------------------------


def test_malformed_request_battery(setup):
    """Poisoned payloads are refused AT SUBMIT with a typed (ValueError-
    compatible) error and never reach a compiled batch."""
    from repro.serve import AdmissionConfig, InvalidRequestError
    params, adapter, x = setup
    srv = make_server(adapter, admission=AdmissionConfig(capacity=8))
    nan = np.asarray(x[0]).copy()
    nan[0, 0, 0] = np.nan
    inf = np.asarray(x[0]).copy()
    inf[-1, -1, -1] = np.inf
    for bad in (nan, inf):
        with pytest.raises(InvalidRequestError):
            srv.submit(Request(uid="bad", kind=PREDICT, x=bad))
        with pytest.raises(ValueError):          # pre-hardening catch sites
            srv.submit(Request(uid="bad", kind=PREDICT, x=bad))
    with pytest.raises(InvalidRequestError, match="shape"):
        srv.submit(Request(uid="shape", kind=PREDICT,
                           x=np.zeros((4, 4, 3), np.float32)))
    with pytest.raises(InvalidRequestError):
        srv.submit(Request(uid="rank", kind=EXPLAIN,
                           x=np.zeros((8, 8), np.float32)))
    assert srv.batcher.pending() == 0            # nothing slipped through
    out = srv.serve([Request(uid="ok", kind=PREDICT, x=x[0])])
    assert out["ok"].ok                          # loop unharmed


def test_dispatch_failure_is_fault_isolated(setup):
    """An adapter exception mid-batch becomes per-request error responses;
    the worker loop survives and keeps serving."""
    params, _, x = setup
    adapter = CNNAdapter(params, CFG)

    def boom(xb):
        raise RuntimeError("device program crashed")
    adapter.predict = boom
    srv = make_server(adapter)
    srv.submit(Request(uid="a", kind=PREDICT, x=x[0]))
    srv.submit(Request(uid="b", kind=PREDICT, x=x[1]))
    out = {r.uid: r for r in srv.drain()}
    assert set(out) == {"a", "b"}
    for r in out.values():
        assert not r.ok and r.error_type == "RuntimeError"
        assert "crashed" in r.error
    assert srv.stats.errors == 2
    del adapter.predict                          # restore the class method
    ok = srv.serve([Request(uid="c", kind=PREDICT, x=x[2])])["c"]
    assert ok.ok and srv.cache.peek("c") is not None


def test_capacity_shed_is_typed_and_serve_folds_it(setup):
    from repro.serve import AdmissionConfig, ShedError
    params, adapter, x = setup
    srv = make_server(adapter, max_delay_s=60.0,
                      admission=AdmissionConfig(capacity=1))
    srv.submit(Request(uid="a", kind=PREDICT, x=x[0]))
    with pytest.raises(ShedError) as ei:
        srv.submit(Request(uid="b", kind=PREDICT, x=x[1]))
    assert ei.value.reason == "queue_full" and ei.value.uid == "b"
    assert srv.stats.sheds["queue_full"] == 1
    # the batch-serve surface returns sheds as structured responses
    out = srv.serve([Request(uid="c", kind=PREDICT, x=x[2])])
    assert out["c"].error_type == "ShedError"
    assert out["c"].meta["shed_reason"] == "queue_full"
    assert out["a"].ok                           # the admitted one completes


def test_degrade_reroutes_to_fxp16_sibling_end_to_end(setup, setup_fxp):
    """Under pressure a float explain reroutes to the quantized sibling:
    the response is flagged, the primary cache stays cold, and the heatmap
    rank-correlates with the float engine's (the certified trade)."""
    from repro.core import fidelity
    from repro.serve import AdmissionConfig, DegradePolicy
    params, adapter, x = setup
    srv = make_server(adapter, max_delay_s=60.0, admission=AdmissionConfig(
        capacity=2, degrade=DegradePolicy(pressure_threshold=0.5,
                                          reroute_precision="fxp16")))
    srv.submit(Request(uid="f", kind=EXPLAIN, x=x[0], method="saliency"))
    rerouted = Request(uid="q", kind=EXPLAIN, x=x[0], method="saliency")
    srv.submit(rerouted)                         # pending 1/2 hits threshold
    assert rerouted.degraded
    out = {r.uid: r for r in srv.drain()}
    assert out["q"].ok and out["q"].meta["degraded"] == "reroute_precision"
    assert "degraded" not in out["f"].meta
    assert srv._degraded_adapter.precision == "fxp16"
    assert srv.cache.peek("q") is None           # never warms the primary
    hm_f = attribution.heatmap(np.asarray(out["f"].relevance)[None])[0]
    hm_q = attribution.heatmap(np.asarray(out["q"].relevance)[None])[0]
    assert fidelity.spearman(np.asarray(hm_f), np.asarray(hm_q)) > 0.8


# ---------------------------------------------------------------------------
# padding cap property + mesh-sharded serving
# ---------------------------------------------------------------------------


from tests._hypothesis_compat import given, settings, st  # noqa: E402
from repro.serve.batcher import pad_size  # noqa: E402


@given(st.integers(min_value=1, max_value=4096),
       st.integers(min_value=1, max_value=64))
@settings(max_examples=200, deadline=None)
def test_pad_size_cap_is_unconditional(n, max_batch):
    """Regression: pad_size used to return the uncapped next power of two
    when n > max_batch, launching shapes no compiled program had."""
    p = pad_size(n, max_batch)
    assert 1 <= p <= max_batch                     # the cap always holds
    assert p >= min(n, max_batch)                  # every popped row seated
    assert p == max_batch or (p & (p - 1)) == 0    # pow2 below the cap
    if n <= max_batch:
        assert p < max(2 * n, 2)                   # and the NEXT pow2


def test_mesh_server_heatmaps_bitwise_with_single_device(setup):
    """Serving through a 1-shard mesh adapter returns heatmaps bitwise
    identical to the single-device adapter for the same requests."""
    params, _, x = setup
    single = CNNAdapter(params, CFG, device="edge-small")
    meshed = CNNAdapter(params, CFG, device="mesh:edge-small:1")
    mk = lambda: [Request(uid=f"r{i}", kind=EXPLAIN, x=x[i],
                          method="saliency") for i in range(3)]
    out_s = make_server(single).serve(mk())
    out_m = make_server(meshed).serve(mk())
    assert out_s.keys() == out_m.keys()
    for uid in out_s:
        assert out_s[uid].ok and out_m[uid].ok
        np.testing.assert_array_equal(np.asarray(out_s[uid].relevance),
                                      np.asarray(out_m[uid].relevance))


@pytest.mark.parametrize("shards", [1, 4])
def test_mesh_server_multi_row_hits_bitwise_with_single_device(setup,
                                                                shards):
    """A 4-row predict on a mesh adapter parks its int32-word residuals in
    the slot table; hits of rows other than 0 (alone, then together) take
    them back by slot and equal the single-device server bitwise."""
    from repro.engine.engine import _Words
    params, _, x = setup
    single = CNNAdapter(params, CFG, device="edge-small")
    meshed = CNNAdapter(params, CFG, device=f"mesh:edge-small:{shards}")
    outs = []
    for adapter in (single, meshed):
        srv = make_server(adapter)
        out = _predict_launch(srv, x, [f"p{i}" for i in range(4)])
        for i in (1, 3):
            out.update(_explain_group(srv, x, [i], method="saliency",
                                      prefix="p"))
        grp = _explain_group(srv, x, [2, 3], method="saliency")
        out.update({f"g{uid}": r for uid, r in grp.items()})
        assert all(r.ok for r in out.values())
        assert all(r.cache_hit for r in out.values() if r.kind == EXPLAIN)
        assert srv.cache.peek("p2").slot == 2
        outs.append((srv, out))
    (_, out_s), (srv_m, out_m) = outs
    row = srv_m.cache.gather([srv_m.cache.peek("p3")], 1)
    words = jax.tree.leaves(row, is_leaf=lambda n: isinstance(n, _Words))
    assert any(isinstance(w, _Words) for w in words)
    assert all(t.dtype == jnp.int32 for t in srv_m.cache._table)
    if shards > 1:          # the table takes the serving mesh's device order
        assert srv_m.cache._mesh == meshed.engine.mesh
    cold = _explain_group(make_server(single), x, [1, 2, 3],
                          method="saliency", prefix="c")
    for key, i in (("p1", 1), ("p3", 3), ("gp2", 2), ("gp3", 3)):
        np.testing.assert_array_equal(np.asarray(out_m[key].relevance),
                                      np.asarray(cold[f"c{i}"].relevance))
    assert out_s.keys() == out_m.keys()
    for key in out_s:
        np.testing.assert_array_equal(np.asarray(out_s[key].logits),
                                      np.asarray(out_m[key].logits))
        if out_s[key].kind == EXPLAIN:
            np.testing.assert_array_equal(
                np.asarray(out_s[key].relevance),
                np.asarray(out_m[key].relevance))


def test_mesh_cache_follows_a_topology_ordered_serving_mesh(monkeypatch):
    """A TPU's serving mesh orders its devices by the chips' topology (a
    v5e 2x2 ring: 0, 1, 3, 2), and a jitted program takes its arguments in
    one device order only: the slot table follows the serving mesh, so a
    hit group from several launches replays as on one device."""
    make_mesh = jax.make_mesh

    def ring(shape, axes, devices=None, **kw):
        devices = list(jax.devices() if devices is None else devices)
        if len(devices) == 4:
            devices = [devices[i] for i in (0, 1, 3, 2)]
        return make_mesh(shape, axes, devices=devices, **kw)

    monkeypatch.setattr(jax, "make_mesh", ring)
    params = cnn.init(jax.random.PRNGKey(5), CFG)     # a fresh engine build
    meshed = CNNAdapter(params, CFG, device="mesh:edge-small:4")
    assert [d.id for d in meshed.engine.mesh.devices.flat] == [0, 1, 3, 2]
    x = _MORE
    srv = make_server(meshed, max_batch=1)             # 4 seats a launch
    _predict_launch(srv, x[:1], ["q0"])
    _predict_launch(srv, x[1:3], ["q1", "q2"])
    _predict_launch(srv, x[3:6], ["q3", "q4", "q5"])
    got = _explain_group(srv, x, [5, 0, 2, 3], method="saliency", prefix="q")
    assert srv.cache._mesh == meshed.engine.mesh
    cold = _explain_group(make_server(CNNAdapter(params, CFG)), x,
                          [5, 0, 2, 3], method="saliency", prefix="c")
    for i in (5, 0, 2, 3):
        assert got[f"q{i}"].ok and got[f"q{i}"].cache_hit
        np.testing.assert_array_equal(np.asarray(got[f"q{i}"].relevance),
                                      np.asarray(cold[f"c{i}"].relevance))
