"""The single injectable monotonic clock for every serving timestamp.

Deadlines, batcher delays, latency stats, and trace spans must all agree
about "now" or deadline decisions and trace timelines drift apart.  Every
component takes a ``clock`` callable defaulting to :func:`monotonic`;
tests and the load-replay harness inject a :class:`VirtualClock` and the
whole stack — spans included — runs on simulated time.
"""
from __future__ import annotations

import time
from typing import Optional, Tuple

#: Default wall clock: monotonic seconds, arbitrary epoch.  The one
#: sanctioned ``time.*`` read for serving-path timestamps.
monotonic = time.monotonic

#: High-resolution timer for measurement loops (benchmark harnesses).
#: Same contract: monotonic seconds.
perf = time.perf_counter

_SCHEDSTAT = "/proc/thread-self/schedstat"


def thread_times() -> Tuple[float, Optional[float]]:
    """(CPU seconds the calling thread has run, seconds it has waited on a
    run queue): ``time.thread_time`` and the second field of Linux's
    ``/proc/thread-self/schedstat``; the wait is ``None`` where that file
    cannot be read."""
    cpu = time.thread_time()
    try:
        with open(_SCHEDSTAT) as f:
            return cpu, int(f.read().split()[1]) / 1e9
    except (OSError, IndexError, ValueError):
        return cpu, None


class VirtualClock:
    """Deterministic manual-advance clock conforming to the ``clock``
    protocol (a zero-arg callable returning monotonic seconds)."""

    def __init__(self, t: float = 0.0):
        self.t = float(t)

    def __call__(self) -> float:
        return self.t

    def advance(self, dt: float) -> float:
        if dt < 0:
            raise ValueError(f"cannot rewind a monotonic clock (dt={dt})")
        self.t += dt
        return self.t
