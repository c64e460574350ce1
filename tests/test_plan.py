"""repro.plan unit + property tests.

Tier-1: profile registry, footprint model sanity, plan legality on the
paper CNN, the constrained-vs-default tile property, tuning-cache
round-trip, and the <1 ms warm replan (no re-measuring).  Slow: hypothesis
sweeps asserting every emitted plan is legal — blocks aligned, dividing
the padded dims, analytic footprint within the profile budget.
"""
import json
import time

import pytest

import repro.configs as configs
from repro.kernels.tiling import LANE, SUBLANE, align_up
from repro.models import cnn
from repro.plan import (InfeasiblePlanError, TuningCache,
                        cnn_plan_footprints, conv2d_fwd_footprint,
                        get_profile, lm_plan_footprints, plan_cnn, plan_lm,
                        plan_vmm, profile_names, ssm_scan_footprint,
                        vmm_fwd_footprint)
from repro.plan import planner as planner_mod
from tests._hypothesis_compat import given, settings, st

PAPER_CFG = cnn.CNNConfig()
TINY_CFG = cnn.CNNConfig(in_hw=(8, 8), in_ch=3, channels=(4, 4), kernel=3,
                         fc=(16,), num_classes=4)
EDGE = get_profile("edge-small")
DETECTED = get_profile("detected")


# ---------------------------------------------------------------------------
# profiles
# ---------------------------------------------------------------------------


def test_profile_registry():
    for name in profile_names():
        p = get_profile(name)
        assert p.vmem_bytes > 0 and p.lane == LANE and p.sublane == SUBLANE
    assert get_profile(None).name == get_profile("detected").name
    assert get_profile(EDGE) is EDGE             # pass-through
    with pytest.raises(ValueError, match="unknown device profile"):
        get_profile("edge-nonexistent")


def test_edge_budgets_are_constrained():
    assert (get_profile("edge-tiny").vmem_bytes
            < get_profile("edge-small").vmem_bytes
            < get_profile("edge-large").vmem_bytes
            < DETECTED.vmem_bytes)


# ---------------------------------------------------------------------------
# analytic cost model
# ---------------------------------------------------------------------------


def test_conv_footprint_grows_with_tile():
    small = conv2d_fwd_footprint(1, 32, 32, 3, 32, 64, 8)
    large = conv2d_fwd_footprint(1, 32, 32, 3, 32, 64, 64)
    assert small.vmem_bytes < large.vmem_bytes
    # smaller tiles reload the input block more often -> more HBM traffic
    assert small.hbm_bytes > large.hbm_bytes


def test_vmm_footprint_precision_widths():
    f32 = vmm_fwd_footprint(8, 4096, 128, 8, 512, 128, precision="f32")
    fxp = vmm_fwd_footprint(8, 4096, 128, 8, 512, 128, precision="fxp16")
    assert fxp.vmem_bytes < f32.vmem_bytes       # 2B operands, same acc
    assert fxp.hbm_bytes < f32.hbm_bytes


def test_tiled_vmem_pads_lanes_and_flat_profiles_do_not():
    """On the v5e profile a conv footprint counts whole (8, 128) VMEM
    tiles — a 32-channel map costs four times its flat bytes — and the
    edge profiles keep the flat on-chip accounting; HBM traffic is the
    same either way."""
    from repro.plan import model as cost
    assert get_profile("tpu-v5e").tiled_vmem and DETECTED.tiled_vmem
    assert not EDGE.tiled_vmem
    assert get_profile("mesh:tpu-v5e:4").tiled_vmem
    assert (cost._vmem((1024, 32), 4, (8, 128))
            == 4 * cost._vmem((1024, 32), 4, None))
    flat = conv2d_fwd_footprint(1, 32, 32, 3, 32, 32, 32)
    tiled = conv2d_fwd_footprint(1, 32, 32, 3, 32, 32, 32, layout=(8, 128))
    assert tiled.vmem_bytes > 2 * flat.vmem_bytes
    assert tiled.hbm_bytes == flat.hbm_bytes
    on_v5e = cnn_plan_footprints(PAPER_CFG, None, seeds=3, profile="tpu-v5e")
    on_edge = cnn_plan_footprints(PAPER_CFG, None, seeds=3, profile=EDGE)
    assert on_v5e["conv1.bwd"].vmem_bytes > on_edge["conv1.bwd"].vmem_bytes


def test_kernel_vmem_limit_is_the_profile_budget():
    """The planned kernels ask the compiler for exactly the budget the
    planner fits tiles into: the detected profile's ``vmem_bytes``."""
    from repro.kernels import compiler_params
    assert compiler_params().vmem_limit_bytes == DETECTED.vmem_bytes


def test_footprint_fits_is_budget_comparison():
    fp = vmm_fwd_footprint(8, 4096, 128, 8, 4096, 128)
    assert fp.fits(DETECTED) and not fp.fits(get_profile("edge-tiny"))


# ---------------------------------------------------------------------------
# planner legality (fixed shapes)
# ---------------------------------------------------------------------------


def _assert_plan_legal(cfg, plan, profile, precision, batch=1, seeds=1):
    fps = cnn_plan_footprints(cfg, plan, precision=precision, batch=batch,
                              seeds=seeds, profile=profile)
    shapes = dict((k, (fam, kw))
                  for k, fam, kw in planner_mod.cnn_kernel_shapes(
                      cfg, batch, seeds))
    assert len(plan) > 0
    for key, tile in plan.entries:
        fam, kw = shapes[key]
        if fam in ("conv2d_fwd", "conv2d_bwd"):
            tco = tile.co_tile
            assert tco % SUBLANE == 0
            cout_p = align_up(kw["cout"], tco)
            assert cout_p % tco == 0             # tile divides padded dim
        else:
            for t in (tile.tk, tile.tn):
                assert t % SUBLANE == 0
            kp = align_up(kw["k"], tile.tk)
            np_ = align_up(kw["n"], tile.tn)
            assert kp % tile.tk == 0 and np_ % tile.tn == 0
        assert fps[key].fits(profile), (key, fps[key], profile.name)
    # the audit covers pool launches too (no knobs, still budgeted)
    for key, fp in fps.items():
        assert fp.fits(profile), (key, fp.vmem_bytes, profile.vmem_bytes)


@pytest.mark.parametrize("precision", ["f32", "fxp16"])
@pytest.mark.parametrize("device", ["detected", "edge-large", "edge-small"])
def test_paper_cnn_plan_legal(device, precision):
    profile = get_profile(device)
    plan = plan_cnn(PAPER_CFG, device=device, precision=precision)
    assert plan.device == profile.name and plan.precision == precision
    _assert_plan_legal(PAPER_CFG, plan, profile, precision)


def test_constrained_profile_splits_what_default_keeps_whole():
    """The paper's design point: per-target resource fitting.  The default
    profile plans FC1's whole 4096-deep contraction as ONE block; the
    constrained edge budgets must split it (never the full-K tile)."""
    k_full = align_up(PAPER_CFG.flat_features(), LANE)
    default_tk = plan_cnn(PAPER_CFG, device="detected").get("fc0.fwd").tk
    assert default_tk == k_full
    # edge-large's 4 MB still holds the full-K block; the 2/1 MB budgets
    # cannot and must split the contraction.  (edge-tiny is probed at the
    # FC shape directly — the paper CNN's f32 conv backward is
    # legitimately infeasible at 1 MB and plan_cnn refuses it whole.)
    edge_small_tk = plan_cnn(PAPER_CFG, device="edge-small").get("fc0.fwd").tk
    assert edge_small_tk < k_full
    tiny_tk = plan_vmm(1, PAPER_CFG.flat_features(), PAPER_CFG.fc[0],
                       profile="edge-tiny").tk
    assert tiny_tk < k_full
    # tighter budget, tighter (or equal) tiles — monotone in the budget
    assert tiny_tk <= edge_small_tk
    with pytest.raises(InfeasiblePlanError):
        plan_cnn(PAPER_CFG, device="edge-tiny")   # conv BP patches > 1 MB


def test_infeasible_budget_raises():
    from repro.plan import DeviceProfile
    nano = DeviceProfile("nano", vmem_bytes=16 * 1024)
    with pytest.raises(InfeasiblePlanError):
        plan_cnn(PAPER_CFG, device=nano)


def test_topk_seeds_scale_bwd_footprints():
    fp1 = cnn_plan_footprints(PAPER_CFG, None, seeds=1)["conv3.bwd"]
    fp5 = cnn_plan_footprints(PAPER_CFG, None, seeds=5)["conv3.bwd"]
    assert fp5.vmem_bytes > fp1.vmem_bytes


# ---------------------------------------------------------------------------
# tuning cache
# ---------------------------------------------------------------------------


def test_cache_roundtrip_and_full_hit(tmp_path):
    cache = TuningCache(str(tmp_path / "tiles.json"))
    plan1 = plan_cnn(PAPER_CFG, device="edge-small", cache=cache)
    assert cache.hits == 0 and cache.misses == len(plan1)
    with open(cache.path) as f:
        stored = json.load(f)
    assert len(stored) == len(plan1)

    warm = TuningCache(cache.path)               # fresh process view
    plan2 = plan_cnn(PAPER_CFG, device="edge-small", cache=warm)
    assert warm.misses == 0 and warm.hits == len(plan1)
    assert plan2 == plan1                        # decoded tiles identical


def test_cache_hit_replans_fast_without_remeasuring(tmp_path, monkeypatch):
    calls = []

    def fake_measure(family, kw, tile, precision):
        calls.append(family)
        return 1.0

    monkeypatch.setattr(planner_mod, "measure_kernel", fake_measure)
    cache = TuningCache(str(tmp_path / "tiles.json"))
    plan1 = plan_cnn(TINY_CFG, device="edge-small", autotune=True,
                     cache=cache)
    assert calls, "cold autotune must measure candidates"

    calls.clear()
    warm = TuningCache(cache.path)
    warm.data                                    # preload off the clock
    best = float("inf")
    for _ in range(20):
        t0 = time.perf_counter()
        plan2 = plan_cnn(TINY_CFG, device="edge-small", autotune=True,
                         cache=warm)
        best = min(best, time.perf_counter() - t0)
    assert not calls, "cache hits must not re-measure"
    assert plan2 == plan1
    assert best < 1e-3, f"warm replan took {best * 1e3:.2f}ms (>1ms)"


def test_analytic_cache_entry_does_not_suppress_autotune(tmp_path,
                                                         monkeypatch):
    calls = []
    monkeypatch.setattr(planner_mod, "measure_kernel",
                        lambda *a: calls.append(a) or 1.0)
    cache = TuningCache(str(tmp_path / "tiles.json"))
    plan_cnn(TINY_CFG, device="edge-small", cache=cache)   # analytic only
    assert not calls
    plan_cnn(TINY_CFG, device="edge-small", autotune=True, cache=cache)
    assert calls, "analytic-only entries must be re-planned with measuring"
    calls.clear()
    plan_cnn(TINY_CFG, device="edge-small", autotune=True, cache=cache)
    assert not calls, "measured entries satisfy autotuned builds"


def test_cache_corrupt_file_reads_empty(tmp_path):
    p = tmp_path / "broken.json"
    p.write_text("{not json")
    cache = TuningCache(str(p))
    assert len(cache) == 0
    assert cache.lookup("k") is None and cache.misses == 1


def test_cache_corruption_recovers_with_atomic_rewrite(tmp_path):
    """A scribbled cache file is logged, dropped, and atomically rewritten
    clean — planning proceeds as a recompute, never a crash."""
    p = tmp_path / "tiles.json"
    for garbage in ('{"k": {"tile": [64', "[1, 2, 3]", '"a string"'):
        p.write_text(garbage)                      # truncated / non-object
        cache = TuningCache(str(p))
        plan = plan_cnn(TINY_CFG, device="edge-small", cache=cache)
        assert cache.hits == 0 and cache.misses == len(plan)
        stored = json.loads(p.read_text())         # rewritten: valid again
        assert len(stored) == len(plan)
        warm = TuningCache(str(p))
        assert plan_cnn(TINY_CFG, device="edge-small", cache=warm) == plan
        assert warm.misses == 0


def test_cache_scribbled_entries_dropped_others_kept(tmp_path):
    cache = TuningCache(str(tmp_path / "tiles.json"))
    plan = plan_cnn(TINY_CFG, device="edge-small", cache=cache)
    stored = json.loads(open(cache.path).read())
    victim = sorted(stored)[0]
    stored[victim] = {"tile": "not-a-list"}        # scribbled value
    stored["foreign|blob"] = 7                     # not even a dict
    stored["bool|tile"] = {"tile": [True, 8]}      # bools are not tile dims
    with open(cache.path, "w") as f:
        json.dump(stored, f)
    warm = TuningCache(cache.path)
    assert len(warm) == len(plan) - 1              # bad entries dropped
    assert plan_cnn(TINY_CFG, device="edge-small", cache=warm) == plan
    assert warm.hits == len(plan) - 1 and warm.misses == 1
    cleaned = json.loads(open(cache.path).read())  # rewritten + replanned
    assert "foreign|blob" not in cleaned and "bool|tile" not in cleaned
    assert TuningCache.valid_entry(cleaned[victim])


def test_cache_wrong_arity_tile_is_replanned_and_repaired(tmp_path):
    """An entry whose tile list passes the schema but decodes to the wrong
    family arity (a cross-family scribble) is replanned, not crashed on."""
    cache = TuningCache(str(tmp_path / "tiles.json"))
    plan = plan_cnn(TINY_CFG, device="edge-small", cache=cache)
    stored = json.loads(open(cache.path).read())
    victim = next(k for k in stored if k.startswith("vmm_fwd"))
    stored[victim]["tile"] = [128]                 # conv-arity blob
    with open(cache.path, "w") as f:
        json.dump(stored, f)
    warm = TuningCache(cache.path)
    assert plan_cnn(TINY_CFG, device="edge-small", cache=warm) == plan
    repaired = json.loads(open(cache.path).read())
    assert len(repaired[victim]["tile"]) == 3      # stored over, full triple
    with pytest.raises(ValueError):
        planner_mod._decode_tile("vmm_fwd", [128])
    with pytest.raises(ValueError):
        planner_mod._decode_tile("no_such_family", [1, 2, 3])


def test_cache_unreadable_path_never_crashes(tmp_path):
    cache = TuningCache(str(tmp_path))             # a DIRECTORY, not a file
    assert len(cache) == 0                         # IsADirectoryError -> {}
    plan = plan_cnn(TINY_CFG, device="edge-small", cache=cache)
    assert len(plan) and cache.misses == len(plan)


# ---------------------------------------------------------------------------
# hypothesis property sweeps (slow tier)
# ---------------------------------------------------------------------------


@pytest.mark.slow
@settings(deadline=None, max_examples=40)
@given(m=st.integers(1, 300), k=st.integers(1, 6000), n=st.integers(1, 600),
       device=st.sampled_from(["detected", "edge-large", "edge-small",
                               "edge-tiny"]),
       precision=st.sampled_from(["f32", "bf16", "fxp16"]))
def test_vmm_plan_always_legal(m, k, n, device, precision):
    profile = get_profile(device)
    t = plan_vmm(m, k, n, profile=profile, precision=precision)
    assert t.tm % SUBLANE == 0 and t.tk % SUBLANE == 0 and t.tn % SUBLANE == 0
    assert align_up(m, t.tm) % t.tm == 0
    assert align_up(k, t.tk) % t.tk == 0
    assert align_up(n, t.tn) % t.tn == 0
    fp = vmm_fwd_footprint(m, k, n, t.tm, t.tk, t.tn, precision=precision,
                           mxu=profile.mxu)
    assert fp.fits(profile)


@pytest.mark.slow
@settings(deadline=None, max_examples=25)
@given(hw=st.sampled_from([8, 16, 32]),
       ch=st.sampled_from([(8,), (4, 8), (16, 16), (8, 16, 32, 32)]),
       fc=st.sampled_from([(), (16,), (64, 32)]),
       classes=st.integers(2, 12),
       seeds=st.integers(1, 3),
       device=st.sampled_from(["detected", "edge-large", "edge-small"]),
       precision=st.sampled_from(["f32", "fxp16"]))
def test_cnn_plan_always_legal(hw, ch, fc, classes, seeds, device,
                               precision):
    pool_every = len(ch) if len(ch) % 2 else 2
    cfg = cnn.CNNConfig(in_hw=(hw, hw), in_ch=3, channels=ch, kernel=3,
                        fc=fc, num_classes=classes, pool_every=pool_every)
    profile = get_profile(device)
    try:
        plan = plan_cnn(cfg, device=device, precision=precision,
                        seeds=seeds)
    except InfeasiblePlanError:
        # legitimate rejection: SOME kernel (e.g. an un-tileable full-map
        # pool/patch term) exceeds the budget at every candidate
        return
    _assert_plan_legal(cfg, plan, profile, precision, seeds=seeds)


# ---------------------------------------------------------------------------
# LM planning: the ssm_scan chunk-length knob
# ---------------------------------------------------------------------------


def test_ssm_scan_footprint_shrinks_with_tiles():
    """Chunking bounds VMEM: the whole-D whole-chunk launch holds the full
    per-(d, chunk) working set; (d_tile, chunk) splits shrink it."""
    whole = ssm_scan_footprint(1, 128, 8192, 16, chunk=128)
    tiled = ssm_scan_footprint(1, 128, 8192, 16, d_tile=1024, chunk=128)
    assert tiled.vmem_bytes < whole.vmem_bytes
    shorter = ssm_scan_footprint(1, 128, 8192, 16, d_tile=1024, chunk=64)
    assert shorter.vmem_bytes < tiled.vmem_bytes


def test_lm_unplanned_full_arch_infeasible_on_edge_small_plan_fits():
    """The PR's acceptance property: the full mamba arch's UNPLANNED scan
    footprint (whole-D, config chunk) blows the edge-small budget; plan_lm
    picks an (d_tile, chunk) that fits it."""
    full = configs.get("falcon-mamba-7b")
    profile = get_profile("edge-small")
    unplanned = lm_plan_footprints(full, None, profile=profile)
    assert len(unplanned) > 0
    assert not all(fp.fits(profile) for fp in unplanned.values())

    plan = plan_lm(full, device="edge-small")
    assert plan.device == "edge-small" and len(plan) == len(unplanned)
    planned = lm_plan_footprints(full, plan, profile=profile)
    assert all(fp.fits(profile) for fp in planned.values())
    for key, tile in plan.entries:
        assert tile.d_tile % SUBLANE == 0 and tile.chunk % SUBLANE == 0
        assert full.d_inner % tile.d_tile == 0


def test_plan_lm_dense_arch_has_no_scan_kernels():
    dense = configs.get_smoke("qwen2-1.5b")
    assert len(plan_lm(dense, device="edge-small")) == 0


def test_plan_lm_infeasible_state_raises():
    from repro.models.config import ModelConfig
    monster = ModelConfig(name="t", family="ssm", n_layers=1, d_model=64,
                          n_heads=2, n_kv=2, d_ff=0, vocab=64,
                          ssm_state=40000, ssm_chunk=16, dtype="float32")
    with pytest.raises(InfeasiblePlanError):
        plan_lm(monster, device="edge-small")


def test_plan_lm_rejects_fxp16():
    cfg = configs.get_smoke("falcon-mamba-7b")
    with pytest.raises(ValueError, match="f32|bf16"):
        plan_lm(cfg, device="edge-small", precision="fxp16")


def test_plan_lm_cache_roundtrip(tmp_path):
    cfg = configs.get_smoke("falcon-mamba-7b")
    cache = TuningCache(str(tmp_path / "tiles.json"))
    plan1 = plan_lm(cfg, device="edge-small", cache=cache)
    assert len(plan1) > 0
    assert cache.hits == 0 and cache.misses == len(plan1)
    warm = TuningCache(cache.path)                 # fresh process view
    plan2 = plan_lm(cfg, device="edge-small", cache=warm)
    assert warm.misses == 0 and warm.hits == len(plan1)
    assert plan2 == plan1


def test_plan_lm_autotune_measures_scan_candidates(tmp_path, monkeypatch):
    calls = []

    def fake_measure(family, kw, tile, precision):
        calls.append(family)
        return 1.0

    monkeypatch.setattr(planner_mod, "measure_kernel", fake_measure)
    cfg = configs.get_smoke("falcon-mamba-7b")
    cache = TuningCache(str(tmp_path / "tiles.json"))
    plan1 = plan_lm(cfg, device="edge-small", autotune=True, cache=cache)
    assert calls and set(calls) == {"ssm_scan"}
    calls.clear()
    warm = TuningCache(cache.path)
    plan2 = plan_lm(cfg, device="edge-small", autotune=True, cache=warm)
    assert not calls, "cache hits must not re-measure"
    assert plan2 == plan1


# ---------------------------------------------------------------------------
# mesh profiles & sharded planning
# ---------------------------------------------------------------------------


def test_mesh_profile_parse_and_per_core_budget():
    from repro.plan import MeshProfile
    p = get_profile("mesh:edge-small:4")
    assert isinstance(p, MeshProfile)
    assert p.n_shards == 4 and p.name == "mesh:edge-small:4"
    # every inherited budget field is PER CORE: a mesh buys parallel
    # shards, never a bigger per-shard working set
    assert p.vmem_bytes == EDGE.vmem_bytes and p.mxu == EDGE.mxu
    assert p.core.name == "edge-small"
    assert get_profile(p) is p                    # pass-through
    assert get_profile("mesh:edge-small:1").n_shards == 1


def test_mesh_profile_rejects_malformed_names():
    from repro.plan import mesh_profile
    for bad in ("mesh:edge-small", "mesh:edge-small:x",
                "mesh:edge-small:0", "mesh:edge-small:4:2"):
        with pytest.raises(ValueError, match="malformed mesh profile"):
            get_profile(bad)
    with pytest.raises(ValueError, match="unknown device profile"):
        get_profile("mesh:edge-nonexistent:4")
    with pytest.raises(ValueError, match="cannot nest"):
        mesh_profile(get_profile("mesh:edge-small:2"), 2)


def test_shard_batch_seeds_batch_first_then_seeds():
    """The batch splits (ceil-divided, as the engine pads it); every shard
    runs the whole seeds axis, as the shard_mapped engine does."""
    from repro.plan import shard_batch_seeds
    assert shard_batch_seeds(8, 16, 4) == (2, 16)   # batch covers the mesh
    assert shard_batch_seeds(2, 16, 4) == (1, 16)   # seeds never split
    assert shard_batch_seeds(1, 3, 4) == (1, 3)     # top-3 panel, batch 1
    assert shard_batch_seeds(1, 1, 4) == (1, 1)     # nothing left to split
    assert shard_batch_seeds(8, 16, 1) == (8, 16)   # single core: identity
    assert shard_batch_seeds(3, 1, 2) == (2, 1)     # ceil remainder slice
    with pytest.raises(ValueError, match="n_shards"):
        shard_batch_seeds(8, 16, 0)


def test_one_shard_mesh_plan_matches_single_core():
    single = plan_cnn(TINY_CFG, device="edge-small", batch=4, seeds=3)
    mesh1 = plan_cnn(TINY_CFG, device="mesh:edge-small:1", batch=4, seeds=3)
    assert mesh1.device == "mesh:edge-small:1"   # extent rides cache keys...
    assert mesh1.entries == single.entries       # ...but tiles are identical


def test_mesh_plan_tiles_the_per_shard_slice():
    """A 4-shard plan of a batch-8 workload tiles the batch-2 slice."""
    whole = plan_cnn(TINY_CFG, device="edge-small", batch=8, seeds=1)
    split = plan_cnn(TINY_CFG, device="mesh:edge-small:4", batch=8, seeds=1)
    local = plan_cnn(TINY_CFG, device="edge-small", batch=2, seeds=1)
    assert split.keys() == whole.keys()
    assert split.entries == local.entries


def test_detect_keys_tpu_profile_on_device_kind(monkeypatch):
    """On a TPU, detect() returns the profile of the device's kind (v5e:
    the published 197 TF/s bf16 and 819 GB/s), and an unknown kind raises
    instead of borrowing another chip's peaks."""
    import jax

    from repro.plan import profiles

    class Device:
        platform = "tpu"
        device_kind = "TPU v5 lite"

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(jax, "devices", lambda *a, **k: [Device()])
    p = profiles.detect()
    assert (p.name, p.mxu_tflops, p.hbm_gbps) == ("tpu-v5e", 197.0, 819.0)
    Device.device_kind = "TPU v99"
    with pytest.raises(ValueError, match="no device profile"):
        profiles.detect()


def test_cpu_detected_profile_is_the_v5e_envelope():
    """Off-TPU, plans are made against the v5e envelope, so a plan made on
    the CPU harness is the plan the chip runs."""
    v5e = get_profile("tpu-v5e")
    assert DETECTED.name == "detected"
    assert ((DETECTED.vmem_bytes, DETECTED.hbm_gbps, DETECTED.mxu_tflops)
            == (v5e.vmem_bytes, v5e.hbm_gbps, v5e.mxu_tflops))
