"""The trace reduction: busy union, idle share, per-family kernel time,
program classification and launches rebuilt from the server's spans."""
import random

import pytest

import _chipbench_path  # noqa: F401
from chipbench import tracing
from chipbench.context import Launch, launches_from_spans, pad_rows
from chipbench.tracing import DeviceTrace, Event


def _trace():
    # device 0: forward module [0, 100) runs two conv kernels and a relu;
    # replay module [300, 360) runs one fused backward; overlap in [10, 20)
    ops0 = [Event(0, 20, "fusion.1", "fusion.1 long_name=%fusion.1 = f32[8]"),
            Event(10, 30, "custom-call.2",
                  "custom-call.2 long_name=... kernel_name=_conv_kernel ..."),
            Event(60, 20, "custom-call.3", "custom-call.3 _conv_kernel"),
            Event(90, 10, "custom-call.4", "custom-call.4 _relu_fwd_kernel"),
            Event(300, 50, "custom-call.9",
                  "custom-call.9 _conv_bwd_fused_kernel")]
    mods0 = [Event(0, 100, "jit_forward(1)"), Event(300, 60, "jit_backward(2)")]
    ops1 = [Event(0, 40, "custom-call.2", "_conv_kernel")]
    return DeviceTrace(window_s=1e-6,
                       ops={"/device:TPU:0": ops0, "/device:TPU:1": ops1},
                       modules={"/device:TPU:0": mods0, "/device:TPU:1": []},
                       host=[Event(100, 200, "client.wait"),
                             Event(0, 1000, "window")])


def test_busy_union_and_idle():
    tr = _trace()
    # [0, 40) + [60, 80) + [90, 100) + [300, 350)
    assert tracing.union_ns(tr.ops["/device:TPU:0"]) == 40 + 20 + 10 + 50
    assert tracing.busy_s(tr) == pytest.approx((120 + 40) / 2 / 1e9)
    idle = 1 - tracing.busy_s(tr) / tr.window_s
    assert idle == pytest.approx(1 - 80e-9 / 1e-6)


def test_family_time_and_modules():
    tr = _trace()
    assert tracing.family_seconds(tr, [r"\b_conv_kernel\b"]) == pytest.approx(
        (30 + 20 + 40) / 1e9)
    assert tracing.family_seconds(
        tr, [r"\b_conv_bwd_fused_kernel\b"]) == pytest.approx(50e-9)
    fwd, n = tracing.modules_by_kernels(tr, [r"\b_conv_kernel\b"])
    assert (fwd, n) == (pytest.approx(100e-9), 1)
    bwd, n = tracing.modules_by_kernels(tr, [r"_conv_bwd_fused"])
    assert (bwd, n) == (pytest.approx(60e-9), 1)


def test_top_ops_and_idle_gaps():
    tr = _trace()
    top = tracing.top_ops(tr, 2)
    assert top[0][0] == "custom-call.2"
    assert top[0][1] == pytest.approx((30 + 40) / 2 / 1e9)
    gaps = tracing.idle_gaps(tr)
    # gaps [40, 60) and [80, 90) lie inside "window" alone; [100, 300) is
    # named by the shorter "client.wait"
    assert gaps == [["client.wait", pytest.approx(200e-9)],
                    ["window", pytest.approx(30e-9)]]


class _Span:
    def __init__(self, name, cat, trace_id, args, duration=0.001):
        self.name, self.cat, self.trace_id = name, cat, trace_id
        self.args, self.duration = args, duration


class _Rec:
    def __init__(self, topk):
        self.topk = topk


class _Window:
    def __init__(self, recs):
        self.recs = recs


def test_launches_from_spans():
    spans = [_Span("batch/predict", "batch", "batch#1", {"n": 3}),
             _Span("batch/explain", "batch", "batch#2",
                   {"n": 3, "method": "guided"})]
    for i in range(3):
        spans += [_Span("request/predict", "request", f"p{i}",
                        {"uid": f"u{i}"}),
                  _Span("engine", "engine", f"p{i}", {"batch": "batch#1"}),
                  _Span("cache", "cache", f"p{i}", {"result": "store"}),
                  _Span("request/explain", "request", f"e{i}",
                        {"uid": f"u{i}"}),
                  _Span("engine", "engine", f"e{i}", {"batch": "batch#2"}),
                  _Span("cache", "cache", f"e{i}",
                        {"result": "hit" if i < 2 else "miss"})]
    window = _Window({(f"u{i}", "explain"): _Rec(3) for i in range(3)})
    got = launches_from_spans(spans, window, fill_target=8)
    assert sorted(got, key=lambda lc: (lc.program, lc.rows)) == [
        Launch("forward", 1), Launch("forward", 4),
        Launch("replay", 1, 3, "guided"), Launch("replay", 2, 3, "guided")]
    assert [pad_rows(n, 8) for n in (1, 3, 5, 9)] == [1, 4, 8, 8]


def _idle_gaps_by_scan(trace, n=10):
    """``idle_gaps`` by its definition: for each gap of the first device,
    every host event is looked at; the shortest that covers the gap's
    middle (the first in order of start among equals) names it."""
    evs = sorted(trace.ops[trace.devices[0]], key=lambda e: e.start_ns)
    host = sorted(trace.host, key=lambda h: h.start_ns)
    by_label, end = {}, None
    for ev in evs:
        if end is not None and ev.start_ns > end:
            mid = (end + ev.start_ns) / 2
            inside = [h for h in host if h.start_ns <= mid <= h.end_ns]
            label = (min(inside, key=lambda h: h.dur_ns).name if inside
                     else "no host event")
            by_label[label] = by_label.get(label, 0.0) + (ev.start_ns - end)
        end = ev.end_ns if end is None else max(end, ev.end_ns)
    top = sorted(by_label.items(), key=lambda kv: -kv[1])[:n]
    return [[label, ns / 1e9] for label, ns in top]


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_idle_gaps_names_each_gap_by_its_shortest_covering_event(seed):
    """On random traces with nested, tied and long host events, the sweep
    reads what looking at every host event for every gap reads."""
    r = random.Random(seed)
    ops = [Event(r.randrange(0, 20000, 10), r.randrange(1, 60), "op")
           for _ in range(300)]
    host = [Event(r.randrange(0, 20000, 10), r.choice([5, 10, 10, 40, 400]),
                  f"h{r.randrange(12)}") for _ in range(600)]
    host += [Event(r.randrange(0, 15000), 5000, "server.poll")
             for _ in range(3)]
    tr = DeviceTrace(window_s=2e-5, ops={"/device:TPU:0": ops,
                                         "/device:TPU:1": ops[:5]},
                     modules={}, host=host)
    assert tracing.idle_gaps(tr, 12) == _idle_gaps_by_scan(tr, 12)
