"""The dispatch and residual-cache readers on a hand-made trace and span
list, and the launches the harness rebuilds from a traced server's spans,
which the server's phase spans leave as they were."""
import jax
import pytest

import _chipbench_path  # noqa: F401
from _chipbench_path import HARNESS
from chipbench import cell as cell_lib
from chipbench.context import Launch, RunContext, launches_from_spans
from chipbench.tracing import DeviceTrace, Event

NEW = ("dispatch.host_ms_p50", "dispatch.offcpu_share", "cache.store_us_p50")


class _Span:
    def __init__(self, name, cat, duration, args=None):
        self.name, self.cat, self.duration = name, cat, duration
        self.args = args or {}


def _ctx(spans=(), trace=None):
    return RunContext(model={}, precision="f32", chips=1, shards=1, peak={},
                      window=None, server=None, spans=list(spans),
                      trace=trace)


def _read(name, ctx):
    return cell_lib.metric_reader(name, HARNESS)(ctx)


def _trace():
    """Three launches on the host plane (ns): [0, 1000) holds ops [100,
    300) and [200, 400), [2000, 2600) holds [2500, 2700), which runs past
    its end; [5000, 5400) holds none.  The second device and other host
    events are not read."""
    ops0 = [Event(100, 200, "conv2d_fwd.1"), Event(200, 200, "vmm_fwd.2"),
            Event(1500, 100, "conv2d_bwd.3"), Event(2500, 200, "vmm_bwd.4")]
    host = [Event(0, 1000, "batch/predict"), Event(2000, 600, "batch/explain"),
            Event(5000, 400, "batch/explain"), Event(0, 9000, "server.poll"),
            Event(100, 50, "engine.forward")]
    return DeviceTrace(window_s=1e-5,
                       ops={"/device:TPU:0": ops0,
                            "/device:TPU:1": [Event(0, 5000, "x")]},
                       modules={}, host=host)


def test_dispatch_host_ms_is_the_median_launch_less_its_device_time():
    # host time per launch: 1000 - 300, 600 - 100, 400 - 0 ns
    assert _read("dispatch.host_ms_p50", _ctx(trace=_trace())) == (
        pytest.approx(500e-6))


def test_dispatch_offcpu_share_sums_over_launches():
    spans = [_Span("batch/predict", "batch", 0.004, {"cpu_s": 0.001}),
             _Span("batch/explain", "batch", 0.006, {"cpu_s": 0.005}),
             _Span("engine.forward", "phase", 0.003, {"cpu_s": 0.0}),
             _Span("queued", "queue", 0.5)]
    # (0.003 + 0.001) / (0.004 + 0.006)
    assert _read("dispatch.offcpu_share", _ctx(spans)) == pytest.approx(0.4)


def test_cache_store_us_is_the_median_store_phase():
    spans = [_Span("cache.store", "phase", d) for d in (3e-3, 1e-3, 2e-3)]
    spans += [_Span("cache", "cache", 1.0), _Span("respond", "phase", 1.0)]
    assert _read("cache.store_us_p50", _ctx(spans)) == pytest.approx(2000.0)


@pytest.mark.parametrize("name", NEW)
def test_reads_nothing_from_a_program_without_phase_spans(name):
    """What a program without scoped spans leaves: no ``batch/*`` host
    events, batch spans with no ``cpu_s``, no phase spans."""
    tr = _trace()
    tr.host = [h for h in tr.host if not h.name.startswith("batch/")]
    spans = [_Span("batch/predict", "batch", 0.004, {"n": 1}),
             _Span("cache", "cache", 0.0, {"result": "store"})]
    assert _read(name, _ctx(spans, tr)) is None
    assert _read(name, _ctx()) is None


class _Rec:
    def __init__(self, topk):
        self.topk = topk


class _Window:
    def __init__(self, recs):
        self.recs = recs


def test_phases_leave_launches_and_dispatch_seconds_unchanged():
    """A traced server's spans give the same launches and dispatch seconds
    with its phase spans as without them (the spans a server without
    phases leaves), and the same launches its engine phases name."""
    from repro.models import cnn
    from repro.obs import Tracer
    from repro.serve import CNNAdapter, ExplanationServer, Request
    cfg = cnn.CNNConfig(in_hw=(8, 8), channels=(4, 4), fc=(16,))
    tracer = Tracer()
    server = ExplanationServer(
        CNNAdapter(cnn.init(jax.random.PRNGKey(0), cfg), cfg), max_batch=4,
        max_delay_s=0.0, tracer=tracer)
    xs = jax.random.normal(jax.random.PRNGKey(1), (6, 8, 8, 3))
    topk = {f"u{i}": (3 if i % 2 else None) for i in range(6)}
    for i in range(3):
        server.submit(Request(uid=f"u{i}", kind="predict", x=xs[i]))
    server.drain()
    for i in range(6):                     # three hits, three cold
        server.submit(Request(uid=f"u{i}", kind="explain", x=xs[i],
                              method="guided", topk=topk[f"u{i}"]))
    assert all(r.ok for r in server.drain())
    spans = tracer.spans
    window = _Window({(u, "explain"): _Rec(k) for u, k in topk.items()})
    plain = [s for s in spans if s.cat != "phase"]
    assert len(plain) < len(spans)

    def key(launches):
        return sorted((lc.program, lc.rows, lc.seeds, lc.method)
                      for lc in launches)

    got = launches_from_spans(spans, window, server.batcher.fill_target)
    assert key(got) == key(launches_from_spans(
        plain, window, server.batcher.fill_target))
    assert _ctx(spans).dispatch_s() == _ctx(plain).dispatch_s() > 0
    named = [Launch(s.args["program"], s.args["rows"],
                    s.args["seeds"] if s.args["program"] == "replay" else 1,
                    s.args["method"] if s.args["program"] == "replay"
                    else "saliency")
             for s in spans if s.cat == "phase" and "program" in s.args]
    assert key(got) == key(named)
    assert {lc.program for lc in got} == {"forward", "replay"}

