"""Percentiles of the harness, with failed requests as misses."""
import _chipbench_path  # noqa: F401
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

