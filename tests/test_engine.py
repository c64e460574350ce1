"""repro.engine: configure -> build -> explain lifecycle.

Covers the build cache (equal specs share one compiled engine; changing any
field rebuilds), backend auto-selection (fxp16 -> manual pair with NO
``backward=`` at any call site), parity of the engine surface with the
legacy free functions, the jit-vs-eager bitwise convention (see
``tests/conftest.py``), and the satellite regressions: manual-``backward=``
through ``contrastive`` / ``attribute_tokens``, and pytree ``heatmap``.
"""
from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import engine as engine_lib
from repro.core import attribution
from repro.engine import (CNNModel, EngineSpec, Fixed, TopK, VjpBackward,
                          build)
from repro.engine.backward import BackwardEngine, ManualSeedBatchedBackward
from repro.models import cnn

CFG = cnn.CNNConfig(in_hw=(8, 8), channels=(4, 4), fc=(16,))


@pytest.fixture(scope="module")
def setup():
    params = cnn.init(jax.random.PRNGKey(0), CFG)
    x = jax.random.normal(jax.random.PRNGKey(1), (3, 8, 8, 3))
    return params, x


def spec_for(params, **kw):
    kw.setdefault("model", CNNModel(params, CFG))
    return EngineSpec(**kw)


# ---------------------------------------------------------------------------
# build cache: rebuild-vs-reuse semantics
# ---------------------------------------------------------------------------


def test_equal_specs_share_one_engine(setup):
    """Two build() calls with equal specs reuse the SAME compiled engine."""
    params, _ = setup
    e1 = build(spec_for(params, method="guided"))
    e2 = build(spec_for(params, method="guided"))      # fresh spec objects
    assert e1 is e2
    assert e1.backend is e2.backend                    # shared compiled pair


def test_changing_any_spec_field_rebuilds(setup):
    params, _ = setup
    base = spec_for(params, method="guided")
    eng = build(base)
    for changed in (
            replace(base, method="saliency"),
            replace(base, precision="bf16"),
            replace(base, backward="vjp"),
            replace(base, targets=TopK(3)),
            replace(base, batch=4),
            replace(base, model=CNNModel(params, CFG, use_pallas=False)),
    ):
        assert changed != base
        other = build(changed)
        assert other is not eng
        assert other.backend is not eng.backend


def test_model_identity_not_value_drives_the_cache(setup):
    """Same params OBJECT -> cache hit; a fresh params tree -> rebuild."""
    params, _ = setup
    assert build(spec_for(params)) is build(spec_for(params))
    params2 = cnn.init(jax.random.PRNGKey(0), CFG)     # equal values, new tree
    assert build(spec_for(params2)) is not build(spec_for(params))


def test_clear_cache_forces_fresh_build(setup):
    params, _ = setup
    spec = spec_for(params, method="deconvnet")
    e1 = build(spec)
    engine_lib.clear_cache()
    assert engine_lib.cache_size() == 0
    assert build(spec) is not e1


def test_spec_validation():
    params = cnn.init(jax.random.PRNGKey(0), CFG)
    with pytest.raises(ValueError):
        spec_for(params, method="lrp")
    with pytest.raises(ValueError):
        spec_for(params, precision="int4")
    with pytest.raises(ValueError):
        spec_for(params, precision="fxp16", backward="vjp")
    with pytest.raises(ValueError):
        spec_for(params, batch=0)
    with pytest.raises(ValueError):
        TopK(0)
    # fxp16 needs the pallas pair: the lax reference model cannot serve it
    bad = spec_for(params, precision="fxp16",
                   model=CNNModel(params, CFG, use_pallas=False))
    with pytest.raises(ValueError):
        bad.resolve_backward()


# ---------------------------------------------------------------------------
# backend resolution + protocol
# ---------------------------------------------------------------------------


def test_backend_auto_selection(setup):
    params, _ = setup
    manual = build(spec_for(params))
    assert isinstance(manual.backend, ManualSeedBatchedBackward)
    assert manual.supports_replay
    vjp = build(spec_for(params, model=CNNModel(params, CFG,
                                                use_pallas=False)))
    assert isinstance(vjp.backend, VjpBackward)
    assert not vjp.supports_replay
    forced = build(spec_for(params, backward="vjp"))
    assert isinstance(forced.backend, VjpBackward)
    quant = build(spec_for(params, precision="fxp16"))
    assert isinstance(quant.backend, ManualSeedBatchedBackward)
    for eng in (manual, vjp, forced, quant):
        assert isinstance(eng.backend, BackwardEngine)   # runtime protocol


def test_vjp_backward_is_a_valid_manual_pair(setup):
    """VjpBackward satisfies the pair contract the manual engines use: the
    free functions accept it via ``backward=`` and reproduce plain vjp."""
    params, x = setup
    f = lambda v: cnn.apply(params, v, CFG, method="saliency")
    pair = VjpBackward(f)
    logits_m, rel_m = attribution.attribute(pair.forward, x,
                                            backward=pair.backward)
    logits_d, rel_d = attribution.attribute(f, x)
    np.testing.assert_allclose(np.asarray(rel_m), np.asarray(rel_d),
                               atol=1e-6)
    np.testing.assert_allclose(np.asarray(logits_m), np.asarray(logits_d),
                               atol=1e-6)


# ---------------------------------------------------------------------------
# explain parity + jit-vs-eager convention
# ---------------------------------------------------------------------------


def test_engine_explain_matches_legacy_free_function(setup):
    """Engine (jitted pair) vs legacy eager pair: same program family,
    tolerance per the conftest jit-vs-eager convention."""
    params, x = setup
    eng = build(spec_for(params, method="guided"))
    logits_e, rel_e = eng.explain(x)
    fwd, bwd = cnn.seed_batched_attribution(params, CFG, "guided")
    logits_l, rel_l = attribution.attribute(fwd, x, backward=bwd)
    np.testing.assert_allclose(np.asarray(rel_e), np.asarray(rel_l),
                               atol=1e-6)
    np.testing.assert_allclose(np.asarray(logits_e), np.asarray(logits_l),
                               atol=1e-6)


def test_engine_jit_vs_jit_is_bitwise(setup):
    """Same compiled program, same inputs -> bitwise equality (and the
    build cache guarantees it IS the same program)."""
    params, x = setup
    e1 = build(spec_for(params, method="guided"))
    e2 = build(spec_for(params, method="guided"))
    l1, r1 = e1.explain(x)
    l2, r2 = e2.explain(x)
    np.testing.assert_array_equal(np.asarray(r1), np.asarray(r2))
    np.testing.assert_array_equal(np.asarray(l1), np.asarray(l2))


def test_topk_spec_and_override(setup):
    params, x = setup
    eng = build(spec_for(params, targets=TopK(3)))
    logits, panel = eng.explain(x)                     # spec fan-out
    assert panel.shape == (3,) + x.shape
    # panel rows equal the explicit attribute_classes maps per example
    top3 = np.argsort(-np.asarray(logits)[0])[:3]
    _, rels = eng.attribute_classes(x[:1], jnp.asarray(top3))
    np.testing.assert_allclose(np.asarray(panel[:, :1]), np.asarray(rels),
                               atol=1e-6)
    # per-call override beats the spec
    _, single = eng.explain(x, target=0)
    assert single.shape == x.shape


def test_fixed_target_spec(setup):
    params, x = setup
    eng = build(spec_for(params, targets=Fixed(7)))
    _, rel_spec = eng.explain(x)
    _, rel_arg = build(spec_for(params)).explain(x, target=7)
    np.testing.assert_array_equal(np.asarray(rel_spec), np.asarray(rel_arg))


def test_static_batch_padding(setup):
    """spec.batch pads the program shape; per-example results unchanged."""
    params, x = setup
    padded = build(spec_for(params, batch=4))
    plain = build(spec_for(params))
    lp, rp = padded.explain(x)                         # 3 -> padded to 4
    ln, rn = plain.explain(x)
    assert lp.shape == (3, CFG.num_classes) and rp.shape == x.shape
    np.testing.assert_allclose(np.asarray(rp), np.asarray(rn), atol=1e-6)
    with pytest.raises(ValueError):
        padded.explain(jnp.concatenate([x, x]))        # 6 > spec.batch


def test_static_batch_pads_per_example_targets(setup):
    """Regression: a [live]-shaped target array must pad alongside the
    batch (both backends), not crash the seed broadcast."""
    params, x = setup
    t = jnp.asarray([1, 2, 3])
    for model in (CNNModel(params, CFG), CNNModel(params, CFG,
                                                  use_pallas=False)):
        padded = build(spec_for(params, model=model, batch=4))
        plain = build(spec_for(params, model=model))
        _, rp = padded.explain(x, target=t)
        _, rn = plain.explain(x, target=t)
        assert rp.shape == x.shape
        np.testing.assert_allclose(np.asarray(rp), np.asarray(rn), atol=1e-6)


def test_predict_then_explain_residuals_replay(setup):
    """The two-phase form returns residuals that replay MORE targets later
    without another forward — and bitwise-match the one-shot explain."""
    params, x = setup
    eng = build(spec_for(params))
    logits, rel, res = eng.predict_then_explain(x)
    _, rel_direct = eng.explain(x)
    np.testing.assert_array_equal(np.asarray(rel), np.asarray(rel_direct))
    seeds = jax.nn.one_hot(jnp.full((1, x.shape[0]), 5), CFG.num_classes)
    rel5 = eng.replay(res, seeds)[0]
    _, rel5_direct = eng.explain(x, target=5)
    np.testing.assert_array_equal(np.asarray(rel5), np.asarray(rel5_direct))


# ---------------------------------------------------------------------------
# fxp16: the whole point — no caller ever passes backward=
# ---------------------------------------------------------------------------


def test_fxp16_explain_without_backward_kwarg(setup):
    params, x = setup
    eng = build(spec_for(params, precision="fxp16", method="guided"))
    logits, rel = eng.explain(x)
    assert rel.shape == x.shape and rel.dtype == jnp.float32
    assert bool(jnp.isfinite(rel).all()) and float(jnp.abs(rel).sum()) > 0
    # parity with the legacy hand-threaded pair
    fwd, bwd = cnn.seed_batched_attribution_jittable(params, CFG, "guided",
                                                     "fxp16")
    _, rel_l = attribution.attribute(jax.jit(fwd), x, backward=jax.jit(bwd))
    np.testing.assert_array_equal(np.asarray(rel), np.asarray(rel_l))


def test_fxp16_composites_and_topk(setup):
    params, x = setup
    eng = build(spec_for(params, precision="fxp16", targets=TopK(2)))
    _, panel = eng.explain(x)
    assert panel.shape == (2,) + x.shape
    _, ig = eng.ig(x, steps=4)
    _, ixg = eng.input_x_gradient(x)
    _, sg = eng.smoothgrad(x, jax.random.PRNGKey(3), n=2)
    for rel in (ig, ixg, sg):
        assert rel.shape == x.shape
        assert bool(jnp.isfinite(rel).all())
        assert float(jnp.abs(rel).sum()) > 0


# ---------------------------------------------------------------------------
# satellite: manual backward= through contrastive / attribute_tokens
# ---------------------------------------------------------------------------


def test_contrastive_manual_backward_matches_vjp(setup):
    """contrastive(backward=) replays the difference seed through the
    manual pair and agrees with the vjp path (float, same kernels)."""
    params, x = setup
    a = jnp.zeros((x.shape[0],), jnp.int32)
    b = jnp.full((x.shape[0],), 5, jnp.int32)
    f = lambda v: cnn.apply(params, v, CFG, method="saliency",
                            use_pallas=True)
    _, rel_vjp = attribution.contrastive(f, x, a, b)
    fwd, bwd = cnn.seed_batched_attribution(params, CFG, "saliency")
    _, rel_man = attribution.contrastive(fwd, x, a, b, backward=bwd)
    np.testing.assert_allclose(np.asarray(rel_man), np.asarray(rel_vjp),
                               atol=1e-5)


def test_contrastive_runs_under_fxp16(setup):
    """Regression: contrastive used to be vjp-only and silently broke under
    precision='fxp16'; through the engine it rides the int16 pair."""
    params, x = setup
    eng = build(spec_for(params, precision="fxp16"))
    a = jnp.zeros((x.shape[0],), jnp.int32)
    b = jnp.full((x.shape[0],), 5, jnp.int32)
    logits, rel = eng.contrastive(x, a, b)
    assert rel.shape == x.shape and rel.dtype == jnp.float32
    assert bool(jnp.isfinite(rel).all()) and float(jnp.abs(rel).sum()) > 0


def test_attribute_tokens_manual_backward_matches_vjp():
    """Regression: attribute_tokens used to be vjp-only; the manual-pair
    route must produce the same relevance/scores."""
    key = jax.random.PRNGKey(0)
    w = jax.random.normal(key, (6, 11), jnp.float32) * 0.3
    h = jax.random.normal(jax.random.PRNGKey(1), (2, 5, 6), jnp.float32)
    f = lambda e: jnp.tanh(e) @ w
    pair = VjpBackward(f)
    lg_v, rel_v, sc_v = attribution.attribute_tokens(f, h)
    lg_m, rel_m, sc_m = attribution.attribute_tokens(
        pair.forward, h, backward=pair.backward)
    np.testing.assert_allclose(np.asarray(rel_m), np.asarray(rel_v),
                               atol=1e-6)
    np.testing.assert_allclose(np.asarray(sc_m), np.asarray(sc_v),
                               atol=1e-5)
    # explicit position/target thread through the manual route too
    _, rel_p, _ = attribution.attribute_tokens(
        pair.forward, h, position=2, target=jnp.asarray([3, 4]),
        backward=pair.backward)
    _, rel_pv, _ = attribution.attribute_tokens(
        f, h, position=2, target=jnp.asarray([3, 4]))
    np.testing.assert_allclose(np.asarray(rel_p), np.asarray(rel_pv),
                               atol=1e-6)


# ---------------------------------------------------------------------------
# satellite: pytree heatmap
# ---------------------------------------------------------------------------


def test_heatmap_accepts_pytree_relevances(setup):
    """heatmap() maps per-leaf, matching attribute()'s pytree contract."""
    params, x = setup
    g = lambda d: cnn.apply(params, d["img"], CFG, method="saliency")
    _, rel = attribution.attribute(g, {"img": x})
    hm = attribution.heatmap(rel)
    assert set(hm) == {"img"}
    assert hm["img"].shape == (3, 8, 8)
    np.testing.assert_array_equal(np.asarray(hm["img"]),
                                  np.asarray(attribution.heatmap(rel["img"])))
    # multi-leaf trees normalize each leaf independently
    hm2 = attribution.heatmap({"a": rel["img"], "b": 2.0 * rel["img"]})
    np.testing.assert_allclose(np.asarray(hm2["a"]), np.asarray(hm2["b"]),
                               atol=1e-6)
    assert float(hm2["a"].min()) >= 0 and float(hm2["a"].max()) <= 1 + 1e-6


# ---------------------------------------------------------------------------
# serve integration: adapters are engine-backed
# ---------------------------------------------------------------------------


def test_adapter_engines_come_from_the_build_cache(setup):
    from repro.serve import CNNAdapter
    params, x = setup
    eng = build(spec_for(params, method="saliency"))
    adapter = CNNAdapter.from_engine(eng)
    assert adapter.engine is eng                       # cache round-trip
    assert adapter.engine_for("guided") is build(
        spec_for(params, method="guided"))
    # registry explainers ride the adapter's engines
    from repro.serve import registry
    expl = registry.get("guided").from_engine(adapter.engine_for("guided"))
    assert expl.engine is adapter.engine_for("guided")
    assert expl.backward is None                       # float -> vjp
    qadapter = CNNAdapter(params, CFG, precision="fxp16")
    assert qadapter.manual_backward("guided") is not None   # int16 -> manual


def test_from_engine_preserves_the_configured_engine(setup):
    """Regression: from_engine must serve the engine AS CONFIGURED (e.g. a
    deliberate lax/vjp reference model), not rebuild a default spec."""
    from repro.serve import CNNAdapter
    params, x = setup
    eng = build(spec_for(params, model=CNNModel(params, CFG,
                                                use_pallas=False)))
    adapter = CNNAdapter.from_engine(eng)
    assert adapter.engine is eng
    assert not adapter.engine.supports_replay            # still the vjp one
    sibling = adapter.engine_for("guided")
    assert not sibling.spec.model.use_pallas             # flags carry over
    logits, residuals = adapter.predict(x)
    rel = adapter.explain_cached(
        "guided", residuals,
        jax.nn.one_hot(jnp.argmax(logits, -1), CFG.num_classes)[None])
    assert rel.shape == (1,) + x.shape


# ---------------------------------------------------------------------------
# folded-batch plan audit (composites under a resolved device plan)
# ---------------------------------------------------------------------------


def test_fold_audit_replans_or_refuses(setup):
    """ig(steps=S)/smoothgrad(n=S) fold S into the batch dim, running the
    planned kernels at M = S*B — a shape resolve_plan never audited.  The
    engine must re-audit at call time: keep the plan when it still fits,
    re-plan when a tile's footprint overflows, and raise
    InfeasiblePlanError (not overrun the budget) when nothing fits."""
    from repro.plan import InfeasiblePlanError
    params, x = setup
    eng = build(spec_for(params, device="edge-small", batch=2))
    x2 = x[:2]
    # folded 16*2=32 rows: every planned tile still fits edge-small
    assert eng._engine_for_fold(16, x2) is eng
    # the audited launch serves the composite with the same answer as an
    # unplanned engine (tiling never changes the math)
    _, rel = eng.ig(x2, steps=32)
    ref_eng = build(spec_for(params))
    _, ref = ref_eng.ig(x2, steps=32)
    np.testing.assert_allclose(np.asarray(rel), np.asarray(ref),
                               rtol=1e-5, atol=1e-6)
    # absurd fold: the tiny CNN's fused FC BP has no tile fitting the 1 MB
    # profile at M=2048 (its m dim rides the grid whole) -> typed refusal
    # from the planner, BEFORE any kernel launch overruns the budget
    with pytest.raises(InfeasiblePlanError):
        eng.ig(x2, steps=1024)


def test_fold_audit_replans_paper_cnn(setup):
    """The paper CNN has a middle regime: at folded M=64 the resolved
    fc0.bwd tile overflows edge-small but a SMALLER tile still fits, so the
    audit re-plans and dispatches through a sibling engine (plan-level
    check only — jit is lazy, nothing compiles here)."""
    paper_cfg = cnn.CNNConfig()
    paper = cnn.init(jax.random.PRNGKey(2), paper_cfg)
    eng = build(EngineSpec(model=CNNModel(paper, paper_cfg),
                           device="edge-small", batch=2))
    xp = jnp.zeros((2, *paper_cfg.in_hw, paper_cfg.in_ch))
    assert eng._engine_for_fold(16, xp) is eng         # folded 32 fits
    sib = eng._engine_for_fold(32, xp)                 # folded 64 replans
    assert sib is not eng
    assert eng._engine_for_fold(32, xp) is sib         # memoized per M
    old, new = eng.plan.get("fc0.bwd"), sib.plan.get("fc0.bwd")
    assert (new.tk, new.tn) != (old.tk, old.tn)
    from repro.plan import InfeasiblePlanError
    with pytest.raises(InfeasiblePlanError):
        eng._engine_for_fold(1024, xp)                 # nothing fits


def test_fold_audit_noop_without_a_plan(setup):
    params, x = setup
    eng = build(spec_for(params))                      # no device plan
    assert eng._plan is None
    assert eng._engine_for_fold(64, x[:2]) is eng


# ---------------------------------------------------------------------------
# mesh-sharded engines
# ---------------------------------------------------------------------------


def test_one_shard_mesh_engine_is_bitwise_single_device(setup):
    """mesh:<p>:1 is the single-device engine plus identity sharding
    constraints — logits AND relevance bitwise equal (acceptance bar for
    the sharded build path)."""
    params, x = setup
    e0 = build(spec_for(params, method="guided", device="edge-small"))
    e1 = build(spec_for(params, method="guided",
                        device="mesh:edge-small:1"))
    assert e0.n_shards == 1 and e0.mesh is None
    assert e1.n_shards == 1 and e1.mesh is not None
    l0, r0 = e0.explain(x)
    l1, r1 = e1.explain(x)
    np.testing.assert_array_equal(np.asarray(r0), np.asarray(r1))
    np.testing.assert_array_equal(np.asarray(l0), np.asarray(l1))


def test_four_shard_mesh_engine_serves_and_matches(setup):
    """A 4-shard mesh over the harness's four virtual devices reports its
    extent (for the batcher's fill target) and serves correct results; the
    batch of 3 pads to 4 rows and is sliced back."""
    params, x = setup
    e4 = build(spec_for(params, method="saliency",
                        device="mesh:edge-small:4"))
    assert e4.n_shards == 4
    e0 = build(spec_for(params, method="saliency", device="edge-small"))
    l0, r0 = e0.explain(x)
    l4, r4 = e4.explain(x)
    np.testing.assert_allclose(np.asarray(r4), np.asarray(r0), atol=1e-6)
    np.testing.assert_allclose(np.asarray(l4), np.asarray(l0), atol=1e-6)


def test_mesh_engine_forward_replay_roundtrip(setup):
    """The residual predict -> cached BP replay path runs sharded too and
    matches the single-device replay bitwise on one shard."""
    params, x = setup
    e0 = build(spec_for(params, method="saliency", device="edge-small"))
    e1 = build(spec_for(params, method="saliency",
                        device="mesh:edge-small:1"))
    logits0, res0 = e0.forward(x)
    logits1, res1 = e1.forward(x)
    np.testing.assert_array_equal(np.asarray(logits0), np.asarray(logits1))
    seeds = jax.nn.one_hot(jnp.argmax(logits0, -1), CFG.num_classes)[None]
    np.testing.assert_array_equal(np.asarray(e0.replay(res0, seeds)),
                                  np.asarray(e1.replay(res1, seeds)))


def test_mesh_residuals_reshard_as_words_and_replay(setup):
    """The 4-shard forward hands out no sub-32-bit residual (packed uint8
    masks leave the shard_map as int32 words), and the residual cache's
    slot-table store -> take round trip replays like one device."""
    from repro.serve.residual_cache import ResidualCache
    params, x = setup
    e0 = build(spec_for(params, method="guided", device="edge-small"))
    e4 = build(spec_for(params, method="guided",
                        device="mesh:edge-small:4"))
    logits0, res0 = e0.forward(x)
    logits4, res4 = e4.forward(x)
    assert all(v.dtype.itemsize >= 4 for v in jax.tree.leaves(res4))
    assert any(v.dtype == jnp.uint8 for v in jax.tree.leaves(res0))
    cache = ResidualCache(capacity=8)
    uids = [f"r{i}" for i in range(x.shape[0])]
    cache.store(uids, np.asarray(logits4), res4, "guided")
    cached = cache.gather([cache.peek(u) for u in uids], x.shape[0])
    seeds = jax.nn.one_hot(jnp.argsort(logits0, -1)[:, ::-1][:, :2].T,
                           CFG.num_classes)
    np.testing.assert_allclose(np.asarray(e4.replay(cached, seeds)),
                               np.asarray(e0.replay(res0, seeds)), atol=1e-6)
    np.testing.assert_allclose(np.asarray(logits4), np.asarray(logits0),
                               atol=1e-6)


@pytest.mark.parametrize("precision", ["f32", "bf16", "fxp16"])
def test_four_shard_mesh_engine_matches_one_device_per_precision(setup,
                                                                 precision):
    """Every precision runs sharded: predict, forward (logits keep their
    dtype — bf16 stays an array, only residuals travel as words), replay
    of a top-2 panel, and explain all match the one-device engine."""
    params, x = setup
    e0 = build(spec_for(params, method="guided", precision=precision,
                        device="edge-small"))
    e4 = build(spec_for(params, method="guided", precision=precision,
                        device="mesh:edge-small:4"))

    def same(a, b):
        assert type(a) is type(b) and a.dtype == b.dtype
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32), atol=1e-6)

    same(e4.predict(x), e0.predict(x))
    logits0, res0 = e0.forward(x)
    logits4, res4 = e4.forward(x)
    same(logits4, logits0)
    seeds = jax.nn.one_hot(
        jnp.argsort(logits0.astype(jnp.float32), -1)[:, ::-1][:, :2].T,
        CFG.num_classes, dtype=logits0.dtype)
    same(e4.replay(res4, seeds), e0.replay(res0, seeds))
    for got, want in zip(e4.explain(x), e0.explain(x)):
        same(got, want)


@pytest.mark.parametrize("dtype,shape", [
    (jnp.uint8, (3, 8, 8, 4)), (jnp.uint8, (2, 5)), (jnp.int16, (3, 7)),
    (jnp.bfloat16, (2, 3, 3))])
def test_words_roundtrip_is_lossless(dtype, shape):
    """_Words carries a sub-32-bit array as int32 words (ragged byte counts
    zero-padded) with its leading axis kept, and unwraps it bit for bit."""
    from repro.engine.engine import _Words
    rng = np.random.RandomState(0)
    v = jnp.asarray(rng.randint(0, 255, shape)).astype(dtype)
    w = _Words.wrap(v)
    assert w.words.dtype == jnp.int32 and w.words.shape[0] == shape[0]
    back = jax.jit(_Words.unwrap)(w)
    assert back.dtype == v.dtype
    np.testing.assert_array_equal(np.asarray(back), np.asarray(v))
    f = jnp.ones((2, 3), jnp.float32)
    assert _Words.wrap(f) is f


def test_adapter_reports_mesh_extent(setup):
    """CNNAdapter surfaces the engine's mesh extent; per-rule siblings and
    from_engine round-trips keep it (the server reads it for fill)."""
    from repro.serve import CNNAdapter
    params, x = setup
    adp = CNNAdapter(params, CFG, device="mesh:edge-small:2")
    assert adp.n_shards == 2
    assert adp.engine_for("guided").n_shards == 2
    assert CNNAdapter.from_engine(adp.engine).n_shards == 2
    assert CNNAdapter(params, CFG, device="edge-small").n_shards == 1
