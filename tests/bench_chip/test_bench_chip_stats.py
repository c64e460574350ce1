"""Percentiles of the harness, with failed requests as misses, and the
rate of explains a window completed."""
import types

import _chipbench_path  # noqa: F401
from chipbench import bench, drive
from chipbench.stats import latencies_with_misses, percentile


def test_percentile_nearest_rank():
    vals = [float(i) for i in range(1, 101)]
    assert percentile(vals, 50) == 51.0     # round(0.5 * 99) = 50 (index)
    assert percentile(vals, 95) == 95.0
    assert percentile(vals, 0) == 1.0 and percentile(vals, 100) == 100.0
    assert percentile([], 50) is None
    assert percentile([3.0], 95) == 3.0


def test_failed_requests_miss_every_limit():
    ok = [0.010] * 95
    lat = latencies_with_misses(ok, 5, 60.0)
    assert len(lat) == 100 and lat[-5:] == [60.0] * 5
    assert percentile(lat, 50) == 0.010
    assert percentile(lat, 96) == 60.0      # a tail past the successes
    # with no failures the tail is the successes' own
    assert percentile(latencies_with_misses(ok, 0, 60.0), 95) == 0.010



def test_explains_per_s_takes_every_explain_to_the_last_answer():
    """A closed loop's last round may end past the close: its explains
    count, over the time to the last answer; a failed one does not."""
    window = drive.Window(server=None, plan=None, seconds=2.0)
    window.t0, window.end, window.closed_t = 10.0, 12.0, 12.5
    for i, (due, lat, ok) in enumerate([(10.0, 1.0, True), (11.0, 1.5, True),
                                        (11.5, 0.5, False),
                                        (10.5, 0.2, True)]):
        window.recs[(f"s{i}", drive.EXPLAIN)] = drive.Rec(
            uid=f"s{i}", kind=drive.EXPLAIN, session=i, method="saliency",
            topk=None, due=due, latency_s=lat, ok=ok)
    window.recs[("s9", drive.PREDICT)] = drive.Rec(
        uid="s9", kind=drive.PREDICT, session=9, method=None, topk=None,
        due=11.0, latency_s=1.9, ok=True)
    cell = types.SimpleNamespace(end_to_end=[
        {"name": "explains_per_s", "unit": "explains/s"},
        {"name": "setup_s", "unit": "s"}])
    got = bench.end_to_end(cell, window, 7.5)
    assert got == {"explains_per_s": {"value": 3 / 2.5, "unit": "explains/s"},
                   "setup_s": {"value": 7.5, "unit": "s"}}
