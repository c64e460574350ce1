"""Dispatch (``ExplanationServer._dispatch``): the median host time of a
launch, in milliseconds.  The program's ``batch/*`` span is scoped, so it is
also an event of the profiler's host plane, on the device trace's clock; a
launch's host time is that event's duration less the union of the first
device's operations inside it.  A program without scoped spans leaves no
such event, and the metric then has nothing to read."""
import bisect

from chipbench.stats import percentile


def read(ctx):
    tr = ctx.trace
    if tr is None or not tr.ops:
        return None
    Event = ctx.tracing.Event
    ops = sorted(tr.ops[tr.devices[0]], key=lambda e: e.start_ns)
    starts = [e.start_ns for e in ops]
    longest = max((e.dur_ns for e in ops), default=0.0)
    host_ns = []
    for h in tr.host:
        if not h.name.startswith("batch/"):
            continue
        lo = bisect.bisect_left(starts, h.start_ns - longest)
        hi = bisect.bisect_left(starts, h.end_ns)
        inside = []
        for e in ops[lo:hi]:
            t0, t1 = max(e.start_ns, h.start_ns), min(e.end_ns, h.end_ns)
            if t1 > t0:
                inside.append(Event(t0, t1 - t0, e.name))
        host_ns.append(h.dur_ns - ctx.tracing.union_ns(inside))
    v = percentile(sorted(host_ns), 50)
    return None if v is None else v / 1e6
