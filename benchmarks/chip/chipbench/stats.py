"""Percentiles of latency samples, with failed requests as misses."""
from __future__ import annotations

from typing import List, Optional, Sequence


def percentile(sorted_vals: Sequence[float], q: float) -> Optional[float]:
    """Nearest-rank percentile of an ascending list (0 <= q <= 100); None
    for an empty list.  The same arithmetic as the server's own counters."""
    if not sorted_vals:
        return None
    idx = min(len(sorted_vals) - 1,
              max(0, int(round(q / 100.0 * (len(sorted_vals) - 1)))))
    return sorted_vals[idx]


def latencies_with_misses(ok_latencies: List[float], n_failed: int,
                          miss_value: float) -> List[float]:
    """Ascending latencies in which every failed request counts as
    ``miss_value``: a time no completed request reaches, so it misses every
    limit a percentile is held to."""
    return sorted(ok_latencies + [miss_value] * n_failed)
