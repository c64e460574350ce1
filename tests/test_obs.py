"""repro.obs: metrics registry, request tracing, clock.

The contracts the observability layer sells:

  * zero-cost when disabled — no tracer means the NULL singletons (no
    allocation, no clock reads, no thread counters), and enabling it
    NEVER changes explain outputs (bitwise);
  * every span terminates on every dispatch path — success, shed at
    submit, expired in queue, degraded — and the Chrome export is
    strict-JSON, schema-valid, Perfetto-loadable;
  * every launch is a ``batch/*`` span with its phases nested inside, and
    every scoped span lands on the profiler's host plane by name and
    ``span_id``, a constant offset from the tracer's clock;
  * the registry guards label cardinality and its snapshot round-trips
    ``json.dumps(..., allow_nan=False)``;
  * the drift table joins cache/fresh measurements against the analytic
    cost model per ``cnn_kernel_shapes`` launch.
"""
import json

import jax
import numpy as np
import pytest

from repro.obs import (NULL_SPAN, NULL_TRACER, Tracer, VirtualClock,
                       dumps_strict, sanitize)
from repro.obs import clock as clock_lib
from repro.obs import metrics as obsm
from repro.obs import registry as obs_registry
from repro.obs.registry import OVERFLOW, Registry, percentile_of
from repro.obs.trace import integrity_errors, validate_chrome
from repro.serve import (AdmissionConfig, CNNAdapter, DegradePolicy,
                         ExplanationServer, Request)
from repro.serve.replay import SimAdapter, replay, synthesize
from repro.serve.stats import percentile

X = np.zeros((8, 8, 1), np.float32)


# ---------------------------------------------------------------------------
# clock
# ---------------------------------------------------------------------------


def test_monotonic_clock_helpers():
    t0 = clock_lib.monotonic()
    assert isinstance(t0, float)
    assert clock_lib.monotonic() >= t0
    assert isinstance(clock_lib.perf(), float)


def test_virtual_clock_conforms_and_refuses_rewind():
    c = VirtualClock()
    assert c() == 0.0
    assert c.advance(1.5) == 1.5
    c.t = max(c.t, 1.0)              # arrivals never move time backwards
    assert c() == 1.5
    with pytest.raises(ValueError):
        c.advance(-0.1)


def test_virtual_clock_reexported_from_replay():
    from repro.serve.replay import VirtualClock as ReplayVC
    assert ReplayVC is VirtualClock


# ---------------------------------------------------------------------------
# strict JSON
# ---------------------------------------------------------------------------


def test_sanitize_maps_nonfinite_to_null():
    obj = {"a": float("nan"), "b": [1.0, float("inf")],
           "c": {"d": float("-inf"), "e": "x"}}
    assert sanitize(obj) == {"a": None, "b": [1.0, None],
                             "c": {"d": None, "e": "x"}}


def test_dumps_strict_rejects_nan():
    with pytest.raises(ValueError):
        dumps_strict({"v": float("nan")})
    assert json.loads(dumps_strict({"v": 1.5})) == {"v": 1.5}


def test_percentile_empty_is_none_not_nan():
    assert percentile([], 50) is None
    assert percentile_of([], 99) is None
    assert percentile([1.0, 2.0, 3.0], 50) == 2.0


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------


def test_counter_gauge_histogram_basics():
    reg = Registry()
    c = reg.counter("c_total", "help", ["kind"])
    c.inc(kind="a")
    c.inc(2.0, kind="a")
    c.inc(kind="b")
    assert c.value(kind="a") == 3.0
    assert c.total() == 4.0

    g = reg.gauge("g", "", ["k"])
    g.set(5.0, k="x")
    g.set_max(3.0, k="x")            # lower: ignored
    g.set_max(9.0, k="x")
    assert g.value(k="x") == 9.0

    h = reg.histogram("h_seconds", "", ["k"])
    for v in (1e-5, 1e-4, 1e-3):
        h.observe(v, k="x")
    snap, = h.snapshot()
    assert snap["count"] == 3
    assert snap["min"] == 1e-5 and snap["max"] == 1e-3
    assert snap["p50"] == 1e-4
    assert snap["buckets"]["+Inf"] == 3
    # cumulative with le (<=) bounds: a value equal to a bound counts in
    assert snap["buckets"]["0.0001"] == 2
    assert snap["buckets"]["0.001"] == 3


def test_registry_snapshot_is_strict_json_and_prometheus_renders():
    reg = Registry()
    reg.counter("x_total", "things", ["kind"]).inc(kind="a")
    reg.histogram("y_seconds", "lat", ["m"]).observe(0.5, m="z")
    snap = reg.snapshot()            # raises if NaN could escape
    json.dumps(snap, allow_nan=False)
    text = reg.render_prometheus()
    assert "# TYPE x_total counter" in text
    assert 'x_total{kind="a"} 1' in text
    assert "# TYPE y_seconds histogram" in text
    assert 'y_seconds_bucket{m="z",le="+Inf"} 1' in text
    assert 'y_seconds_count{m="z"} 1' in text


def test_empty_histogram_snapshot_has_null_percentiles():
    reg = Registry()
    h = reg.histogram("h", "", ["k"])
    h._cell({"k": "empty"})          # series exists, zero observations
    snap, = h.snapshot()
    assert snap["p50"] is None and snap["mean"] is None
    json.dumps(reg.snapshot(), allow_nan=False)


def test_label_cardinality_guard_collapses_overflow():
    reg = Registry(max_label_sets=4)
    c = reg.counter("c_total", "", ["uid"])
    for i in range(10):
        c.inc(uid=f"u{i}")
    assert len(list(c.series())) == 5          # 4 real + 1 overflow
    assert c.overflowed == 6
    assert c.value(uid=OVERFLOW) == 6.0
    # an overflow series also caps gauges/histograms
    h = reg.histogram("h", "", ["uid"])
    for i in range(8):
        h.observe(0.1, uid=f"u{i}")
    assert sum(s["count"] for s in h.snapshot()) == 8


def test_reregistration_idempotent_but_kind_mismatch_raises():
    reg = Registry()
    a = reg.counter("m", "", ["k"])
    assert reg.counter("m", "", ["k"]) is a
    with pytest.raises(ValueError):
        reg.gauge("m", "", ["k"])
    with pytest.raises(ValueError):
        reg.counter("m", "", ["other"])


def test_default_registry_names_all_subsystem_series():
    """The eager catalog means a fresh snapshot names serve, plan-cache,
    and engine-cache series before any traffic."""
    snap = obs_registry.snapshot()
    for name in ("serve_requests_total", "serve_sheds_total",
                 "serve_degrades_total", "serve_residual_cache_events_total",
                 "plan_cache_lookups_total", "engine_builds_total"):
        assert name in snap, name
    sheds = {s["labels"]["reason"]
             for s in snap["serve_sheds_total"]["series"]}
    assert {"queue_full", "rate_limit", "deadline", "expired"} <= sheds
    plans = {s["labels"]["result"]
             for s in snap["plan_cache_lookups_total"]["series"]}
    assert {"hit", "miss"} <= plans
    builds = {s["labels"]["outcome"]
              for s in snap["engine_builds_total"]["series"]}
    assert {"build", "hit", "evict"} <= builds


# ---------------------------------------------------------------------------
# tracer
# ---------------------------------------------------------------------------


def test_span_lifecycle_and_chrome_export():
    clock = VirtualClock()
    tracer = Tracer(clock=clock)
    root = tracer.start("request/explain", cat="request", trace_id="r1",
                        args={"uid": "q0"})
    clock.advance(0.001)
    child = root.child("engine", cat="engine")
    clock.advance(0.002)
    child.end(status="ok")
    root.end(status="ok")
    assert integrity_errors(tracer.spans) == []
    assert child.trace_id == "r1" and child.parent_id == root.span_id
    chrome = tracer.to_chrome()
    assert validate_chrome(chrome) == []
    xs = [e for e in chrome["traceEvents"] if e["ph"] == "X"]
    assert {e["name"] for e in xs} == {"request/explain", "engine"}
    eng, = [e for e in xs if e["name"] == "engine"]
    assert eng["dur"] == pytest.approx(2000.0)      # us
    json.dumps(chrome, allow_nan=False)


def test_tracer_integrity_catches_unterminated_and_dangling():
    tracer = Tracer(clock=VirtualClock())
    s = tracer.start("open", trace_id="t")
    errs = integrity_errors(tracer.spans)
    assert any("unterminated" in e for e in errs)
    s.end()
    s.parent_id = "no-such-span"
    assert any("dangling" in e for e in integrity_errors(tracer.spans))


def test_tracer_finish_terminates_open_spans():
    clock = VirtualClock()
    tracer = Tracer(clock=clock)
    tracer.start("left/open", trace_id="t")
    clock.advance(1.0)
    tracer.finish()
    assert integrity_errors(tracer.spans) == []
    assert tracer.spans[0].args.get("incomplete") is True


def test_null_and_disabled_tracers_allocate_nothing():
    assert NULL_TRACER.start("x") is NULL_SPAN
    assert NULL_SPAN.child("y") is NULL_SPAN
    NULL_SPAN.end(status="ok")       # no-op, idempotent
    assert not NULL_SPAN.enabled
    t = Tracer(enabled=False)
    assert t.start("x") is NULL_SPAN
    assert t.spans == []


def test_tracer_max_spans_bound():
    tracer = Tracer(clock=VirtualClock(), max_spans=3)
    spans = [tracer.start(f"s{i}", trace_id="t") for i in range(5)]
    assert len(tracer.spans) == 3
    assert spans[3] is NULL_SPAN and spans[4] is NULL_SPAN
    assert tracer.dropped == 2
    assert tracer.to_chrome()["otherData"]["dropped_spans"] == 2


# ---------------------------------------------------------------------------
# server tracing: every path terminates its spans
# ---------------------------------------------------------------------------


def traced_sim_replay(n=600, rate=6000.0):
    """Overloaded bursty mix: sheds at submit, expirations in queue,
    degrades, AND successes — all four span-ending paths."""
    clock = VirtualClock()
    tracer = Tracer(clock=clock)
    server = ExplanationServer(
        SimAdapter(clock), max_batch=4, max_delay_s=0.0, clock=clock,
        tracer=tracer,
        admission=AdmissionConfig(
            capacity=16, default_deadline_s=0.05,
            degrade=DegradePolicy(pressure_threshold=0.3,
                                  reroute_precision="fxp16")))
    trace = synthesize(n, rate=rate, arrivals="bursty", seed=7,
                       deadline_s={"predict": 0.05, "explain": 0.1})
    rep = replay(server, trace)
    tracer.finish()
    return tracer, rep, server


def test_traced_mixed_dispatch_span_integrity():
    tracer, rep, server = traced_sim_replay()
    assert rep.shed_total > 0, "fixture must exercise shedding"
    assert rep.completed > 0
    assert integrity_errors(tracer.spans) == []
    roots = [s for s in tracer.spans if s.name.startswith("request/")]
    assert len(roots) == rep.offered
    by_status = {}
    for s in roots:
        by_status[s.args.get("status")] = by_status.get(
            s.args.get("status"), 0) + 1
        assert s.t1 is not None
    assert by_status.get("ok", 0) == rep.completed
    assert by_status.get("shed", 0) == rep.shed_total
    # admitted-and-completed requests carry the full child chain
    ok_tids = {s.trace_id for s in roots if s.args.get("status") == "ok"}
    for name in ("admission", "queued", "engine", "cache"):
        tids = {s.trace_id for s in tracer.spans if s.name == name}
        assert ok_tids <= tids, f"missing {name} spans"
    assert validate_chrome(tracer.to_chrome()) == []


def test_traced_replay_deterministic_span_count():
    t1, _, _ = traced_sim_replay()
    t2, _, _ = traced_sim_replay()
    assert len(t1.spans) == len(t2.spans)
    assert [s.name for s in t1.spans] == [s.name for s in t2.spans]


def test_server_stats_feed_default_registry():
    obs_registry.reset()
    _, rep, server = traced_sim_replay()
    assert obsm.SERVE_REQUESTS.total() == rep.completed
    assert obsm.SERVE_SHEDS.total() == rep.shed_total
    assert obsm.SERVE_BATCHES.total() > 0
    assert obsm.SERVE_QUEUE_PEAK.value() == rep.peak_queue_depth
    json.dumps(obs_registry.snapshot(), allow_nan=False)


# ---------------------------------------------------------------------------
# tracing never changes outputs (bitwise)
# ---------------------------------------------------------------------------


def _cnn_responses(tracer):
    from repro.models import cnn
    cfg = cnn.CNNConfig(in_hw=(8, 8), channels=(4, 4), fc=(16,))
    params = cnn.init(jax.random.PRNGKey(0), cfg)
    server = ExplanationServer(CNNAdapter(params, cfg), max_batch=4,
                               max_delay_s=0.0, tracer=tracer)
    xs = jax.random.normal(jax.random.PRNGKey(1), (3, 8, 8, 3))
    for i in range(3):
        server.submit(Request(uid=f"q{i}", kind="predict", x=xs[i]))
        server.submit(Request(uid=f"q{i}", kind="explain", x=xs[i],
                              method="saliency"))
    return {(r.uid, r.kind): r for r in server.drain()}


@pytest.mark.slow
def test_tracing_bitwise_noop_on_outputs():
    plain = _cnn_responses(None)
    tracer = Tracer()
    traced = _cnn_responses(tracer)
    assert plain.keys() == traced.keys()
    for key, r0 in plain.items():
        r1 = traced[key]
        assert r0.ok and r1.ok
        np.testing.assert_array_equal(np.asarray(r0.logits),
                                      np.asarray(r1.logits))
        if r0.relevance is not None:
            np.testing.assert_array_equal(np.asarray(r0.relevance),
                                          np.asarray(r1.relevance))
    assert len(tracer.spans) > 0
    assert integrity_errors(tracer.spans) == []


# ---------------------------------------------------------------------------
# scoped spans: launch phases, the profiler's clock, thread counters
# ---------------------------------------------------------------------------

#: the phases each kind of launch runs, in order
PHASES = {
    "predict": ["batch.stack", "engine.forward", "cache.store", "respond"],
    "hit": ["targets", "cache.gather", "engine.replay", "respond"],
    "cold": ["batch.stack", "engine.forward", "targets", "engine.replay",
             "cache.store", "respond"],
    "composite": ["batch.stack", "engine.attribute", "respond"],
}


def test_tracer_scope_nests_and_terminates_spans():
    clock = VirtualClock()
    tracer = Tracer(clock=clock)
    with tracer.scope("batch/explain", cat="batch", trace_id="b1",
                      n=2) as outer:
        clock.advance(0.001)
        with tracer.scope("engine.replay", parent=outer, cat="phase",
                          rows=2) as inner:
            clock.advance(0.002)
        assert inner.t1 == pytest.approx(0.003) and outer.t1 is None
        with pytest.raises(RuntimeError):
            with tracer.scope("respond", parent=outer, cat="phase"):
                clock.advance(0.001)
                raise RuntimeError("a poisoned batch")
    assert [s.name for s in tracer.spans] == ["batch/explain",
                                              "engine.replay", "respond"]
    assert all(s.trace_id == "b1" for s in tracer.spans)
    assert [s.parent_id for s in tracer.spans[1:]] == [outer.span_id] * 2
    assert tracer.spans[2].t1 == pytest.approx(0.004)   # ended on raise
    assert outer.args == {"n": 2} and inner.args == {"rows": 2}
    assert (outer.t0, outer.t1) == (0.0, pytest.approx(0.004))
    assert integrity_errors(tracer.spans) == []
    # an explicit end inside the block wins over the scope's own
    with tracer.scope("batch/predict", cat="batch", t0=1.0) as span:
        span.end(t=1.5)
    assert (span.t0, span.t1) == (1.0, 1.5)


def test_null_scope_allocates_nothing():
    import itertools
    import tracemalloc

    from repro.obs import NULL_SCOPE

    def no_clock():
        raise AssertionError("a disabled tracer read the clock")

    off = Tracer(clock=no_clock, enabled=False)
    for tracer in (NULL_TRACER, off):
        assert tracer.scope("batch/explain", cat="batch", n=1) is NULL_SCOPE
        with tracer.scope("engine.replay", parent=NULL_SPAN,
                          cat="phase") as span:
            assert span is NULL_SPAN
    assert off.spans == []

    def grown(calls):
        tracemalloc.start()
        try:
            before = tracemalloc.get_traced_memory()[0]
            for _ in itertools.repeat(None, calls):   # no int per turn
                with NULL_TRACER.scope("engine.forward", parent=NULL_SPAN,
                                       cat="phase", rows=8, live=1) as span:
                    span.end()
            return tracemalloc.get_traced_memory()[0] - before
        finally:
            tracemalloc.stop()

    assert grown(10_000) == grown(10) == 0


@pytest.fixture(scope="module")
def tiny_cnn_adapter():
    from repro.models import cnn
    cfg = cnn.CNNConfig(in_hw=(8, 8), channels=(4, 4), fc=(16,))
    return CNNAdapter(cnn.init(jax.random.PRNGKey(0), cfg), cfg)


def _serve_every_path(adapter, tracer):
    """Predicts, then explains that hit the cache (argmax and a top-3
    panel), cold explains and a composite one: every launch kind.  ->
    {launch kind: batch span ids}"""
    server = ExplanationServer(adapter, max_batch=4, max_delay_s=0.0,
                               tracer=tracer)
    xs = jax.random.normal(jax.random.PRNGKey(1), (6, 8, 8, 3))
    kinds = {}

    def wave(kind, reqs):
        n = len(tracer.spans)
        for r in reqs:
            server.submit(r)
        assert all(r.ok for r in server.drain())
        kinds[kind] = [s.span_id for s in tracer.spans[n:]
                       if s.cat == "batch"]

    wave("predict", [Request(uid=f"q{i}", kind="predict", x=xs[i])
                     for i in range(3)])
    wave("hit", [Request(uid=f"q{i}", kind="explain", x=xs[i],
                         method="guided", topk=3 if i else None)
                 for i in range(3)])
    wave("cold", [Request(uid=f"c{i}", kind="explain", x=xs[3 + i],
                          method="saliency") for i in range(2)])
    wave("composite", [Request(uid="g0", kind="explain", x=xs[5],
                               method="input_x_gradient")])
    return kinds


def test_every_launch_nests_its_phases_in_its_batch_span(tiny_cnn_adapter):
    tracer = Tracer()
    kinds = _serve_every_path(tiny_cnn_adapter, tracer)
    tracer.finish()
    assert integrity_errors(tracer.spans) == []
    assert not any(s.args.get("incomplete") for s in tracer.spans)
    by_id = {s.span_id: s for s in tracer.spans}
    phases = [s for s in tracer.spans if s.cat == "phase"]
    assert all(by_id[s.parent_id].cat == "batch" for s in phases)
    for kind, ids in kinds.items():
        assert ids, kind
        for bid in ids:
            batch = by_id[bid]
            mine = [s for s in phases if s.parent_id == bid]
            assert [s.name for s in mine] == PHASES[kind], kind
            assert all(batch.t0 <= s.t0 <= s.t1 <= batch.t1 for s in mine)
            for s in mine:
                if s.name.startswith("engine."):
                    assert s.args["live"] == batch.args["n"]
                    assert s.args["rows"] >= s.args["live"]
                    assert s.args["method"] == batch.args["method"]
    assert {s.args["seeds"] for s in phases
            if s.name == "engine.replay"} == {1, 3}


def test_scoped_spans_land_on_the_profiler_host_plane(tiny_cnn_adapter,
                                                      tmp_path):
    import glob

    from jax.profiler import ProfileData
    _serve_every_path(tiny_cnn_adapter, Tracer())     # compile outside
    tracer = Tracer()
    jax.profiler.start_trace(str(tmp_path))
    try:
        _serve_every_path(tiny_cnn_adapter, tracer)
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    host = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                for ev in line.events:
                    sid = next((v for k, v in ev.stats if k == "span_id"),
                               None)
                    if sid is not None:
                        host[int(sid)] = (ev.name, ev.start_ns * 1e-9,
                                          ev.duration_ns * 1e-9)
    scoped = [s for s in tracer.spans if s.cat in ("batch", "phase")]
    assert len(scoped) > 20
    offsets = []
    for s in scoped:
        name, start, dur = host[s.span_id]
        assert name == s.name
        assert dur == pytest.approx(s.duration, abs=1e-3)
        offsets.append(start - s.t0)
    assert max(offsets) - min(offsets) < 1e-3


@pytest.mark.parametrize("schedstat", ["readable", "unreadable"])
def test_batch_spans_carry_thread_cpu_and_runq_seconds(tiny_cnn_adapter,
                                                       monkeypatch,
                                                       schedstat):
    import os
    if schedstat == "unreadable":
        monkeypatch.setattr(clock_lib, "_SCHEDSTAT", "/nonexistent/schedstat")
    readable = os.path.exists(clock_lib._SCHEDSTAT)
    tracer = Tracer()
    _serve_every_path(tiny_cnn_adapter, tracer)
    batches = [s for s in tracer.spans if s.cat == "batch"]
    assert batches
    for s in batches:
        assert s.args["cpu_s"] >= 0
        if readable:
            assert s.args["runq_s"] >= 0
        else:
            assert s.args["runq_s"] is None


def test_untraced_server_reads_no_thread_counters(tiny_cnn_adapter,
                                                  monkeypatch):
    def refuse():
        raise AssertionError("an untraced server read a thread counter")

    monkeypatch.setattr(clock_lib, "thread_times", refuse)
    assert _serve_every_path(tiny_cnn_adapter, NULL_TRACER)


# ---------------------------------------------------------------------------
# drift table
# ---------------------------------------------------------------------------


def test_drift_rows_cover_every_launch():
    from repro.models import cnn
    from repro.plan.drift import drift_rows, format_drift
    cfg = cnn.CNNConfig(in_hw=(8, 8), channels=(4, 4), fc=(16,))
    rows = drift_rows(cfg)
    assert rows and all(r["est_us"] > 0 for r in rows)
    families = {r["family"] for r in rows}
    assert {"conv2d_fwd", "conv2d_bwd", "vmm_fwd", "vmm_bwd"} <= families
    assert all(r["measured_us"] is None and r["drift"] is None
               and r["source"] is None for r in rows)
    assert "vmm_fwd" in format_drift(rows)


def test_drift_joins_tuning_cache_and_persists_strict(tmp_path):
    from repro.models import cnn
    from repro.plan.cache import TuningCache, cache_key
    from repro.plan.drift import drift_path, drift_rows, write_drift
    cfg = cnn.CNNConfig(in_hw=(8, 8), channels=(4, 4), fc=(16,))
    cache = TuningCache(str(tmp_path / "tune.json"))
    rows = drift_rows(cfg)
    target = next(r for r in rows if r["family"] == "conv2d_fwd")
    dims = [int(d) for d in target["shape"].split("x")]
    ck = cache_key("conv2d_fwd", dims, "float32", "f32", target["device"])
    cache.store(ck, {"family": "conv2d_fwd", "tile": [4],
                     "measured_us": 42.0})
    joined = drift_rows(cfg, cache=cache)
    hit = next(r for r in joined if r["shape"] == target["shape"]
               and r["family"] == "conv2d_fwd")
    assert hit["source"] == "cache" and hit["measured_us"] == 42.0

    out = write_drift(joined, str(tmp_path / "tune.drift.json"))
    with open(out) as f:
        loaded = json.load(f)
    assert loaded["rows"] == joined
    assert drift_path("/x/y/cache.json") == "/x/y/cache.drift.json"


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------


def test_obs_cli_trace_and_validate(tmp_path, capsys):
    from repro.obs.__main__ import main
    out = str(tmp_path / "t.json")
    metrics = str(tmp_path / "m.json")
    assert main(["trace", "-n", "60", "--out", out,
                 "--metrics-out", metrics]) == 0
    assert main(["validate", out]) == 0
    with open(out) as f:
        chrome = json.load(f)
    assert validate_chrome(chrome) == []
    with open(metrics) as f:
        snap = json.load(f)
    assert "serve_requests_total" in snap
    capsys.readouterr()

    bad = str(tmp_path / "bad.json")
    with open(bad, "w") as f:
        json.dump({"traceEvents": [{"ph": "Q", "name": "x"}]}, f)
    assert main(["validate", bad]) == 1
    capsys.readouterr()
