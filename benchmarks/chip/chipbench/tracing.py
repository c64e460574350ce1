"""The profiler trace of a window, reduced to device events.

``capture`` starts JAX's profiler (no Python call tracing: host time stays
what it is without it) and stops it; ``load`` reads the ``.xplane.pb`` it
wrote with nothing but JAX and keeps, per device plane, two lines:

* ``ops``: every operation that ran on the device (``XLA Ops``), with the
  name and the ``long_name`` stat that holds the HLO instruction, where a
  Pallas kernel's custom call names its kernel function;
* ``modules``: every program execution (``XLA Modules``), named after the
  jitted function;

and the host plane's events (the client's ``TraceAnnotation`` spans and
the runtime's own), on the same timeline, to name what the host did while
the device was idle.

Busy time is the union of the ops' intervals; the reductions here are the
arithmetic every per-layer reader shares.
"""
from __future__ import annotations

import bisect
import glob
import heapq
import os
import re
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Sequence, Tuple

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
_KEEP_STATS = ("long_name", "hlo_op", "tf_op", "hlo_module", "program_id")


@dataclass
class Event:
    start_ns: float
    dur_ns: float
    name: str
    text: str = ""          # name + the kept stats, for pattern matching

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


@dataclass
class DeviceTrace:
    """One traced window: per device plane, its op and module events."""
    window_s: float = 0.0
    ops: Dict[str, List[Event]] = field(default_factory=dict)
    modules: Dict[str, List[Event]] = field(default_factory=dict)
    host: List[Event] = field(default_factory=list)

    @property
    def devices(self) -> List[str]:
        return sorted(self.ops)


def capture_options():
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    return opts


def xplane_file(log_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


def _is_device_plane(name: str) -> bool:
    return name.startswith("/device:") and "TPU" in name


def _text(ev) -> str:
    parts = [ev.name]
    for k, v in ev.stats:
        if k in _KEEP_STATS:
            parts.append(f"{k}={v}")
    return " ".join(parts)


def load(path: str, window_s: float) -> DeviceTrace:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    out = DeviceTrace(window_s=window_s)
    for plane in pd.planes:
        if plane.name.startswith("/host:CPU"):
            out.host.extend(Event(float(ev.start_ns), float(ev.duration_ns),
                                  ev.name)
                            for line in plane.lines for ev in line.events
                            if ev.duration_ns > 0)
            continue
        if not _is_device_plane(plane.name):
            continue
        for line in plane.lines:
            if line.name == OPS_LINE:
                dest = out.ops.setdefault(plane.name, [])
            elif line.name == MODULES_LINE:
                dest = out.modules.setdefault(plane.name, [])
            else:
                continue
            for ev in line.events:
                dest.append(Event(float(ev.start_ns), float(ev.duration_ns),
                                  ev.name, _text(ev)))
        out.ops.setdefault(plane.name, [])
        out.modules.setdefault(plane.name, [])
    return out


def summarize(path: str, per_line: int = 40) -> dict:
    """Planes, lines, event counts and the heaviest event names with a
    sample of their stats: for reading a trace by hand."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    out = []
    for plane in pd.planes:
        lines = []
        for line in plane.lines:
            tot: Dict[str, List[float]] = {}
            sample: Dict[str, List[Tuple[str, str]]] = {}
            n = 0
            for ev in line.events:
                n += 1
                t = tot.setdefault(ev.name, [0, 0.0])
                t[0] += 1
                t[1] += float(ev.duration_ns)
                if ev.name not in sample and len(sample) < per_line:
                    sample[ev.name] = [(k, str(v)[:300]) for k, v in ev.stats]
            top = sorted(tot.items(), key=lambda kv: -kv[1][1])[:per_line]
            lines.append({"line": line.name, "events": n,
                          "top": [[k, c, d, sample.get(k)]
                                  for k, (c, d) in top]})
        out.append({"plane": plane.name, "lines": lines})
    return {"planes": out}


# -- reductions -------------------------------------------------------------------

def union_ns(events: Iterable[Event]) -> float:
    """Length of the union of the events' intervals."""
    total, cur_s, cur_e = 0.0, None, None
    for ev in sorted(events, key=lambda e: e.start_ns):
        if cur_e is None or ev.start_ns > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = ev.start_ns, ev.end_ns
        else:
            cur_e = max(cur_e, ev.end_ns)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def busy_s(trace: DeviceTrace) -> float:
    """Seconds in which an operation ran, averaged over the devices."""
    if not trace.ops:
        return 0.0
    return sum(union_ns(evs) for evs in trace.ops.values()) / (
        1e9 * len(trace.ops))


def matching(events: Iterable[Event], patterns: Sequence[str]) -> List[Event]:
    rx = re.compile("|".join(patterns))
    return [ev for ev in events if rx.search(ev.text)]


def all_ops(trace: DeviceTrace) -> List[Event]:
    return [ev for evs in trace.ops.values() for ev in evs]


def family_seconds(trace: DeviceTrace, patterns: Sequence[str]) -> float:
    """Summed device time of the ops whose name or HLO text matches."""
    return sum(ev.dur_ns for ev in matching(all_ops(trace), patterns)) / 1e9


def modules_by_kernels(trace: DeviceTrace, patterns: Sequence[str]
                       ) -> Tuple[float, int]:
    """Summed device time and count of the program executions that ran at
    least one op matching ``patterns``, over all devices."""
    rx = re.compile("|".join(patterns))
    total, count = 0.0, 0
    for dev, mods in trace.modules.items():
        ops = sorted(trace.ops.get(dev, []), key=lambda e: e.start_ns)
        starts = [e.start_ns for e in ops]
        for m in mods:
            lo = bisect.bisect_left(starts, m.start_ns)
            hi = bisect.bisect_right(starts, m.end_ns)
            if any(rx.search(ops[i].text) for i in range(lo, hi)):
                total += m.dur_ns
                count += 1
    return total / 1e9, count


def top_ops(trace: DeviceTrace, n: int = 10) -> List[List]:
    """The device operations that took most time, seconds averaged over the
    devices."""
    tot: Dict[str, float] = {}
    for ev in all_ops(trace):
        tot[ev.name] = tot.get(ev.name, 0.0) + ev.dur_ns
    k = max(len(trace.ops), 1)
    return [[name, d / 1e9 / k]
            for name, d in sorted(tot.items(), key=lambda kv: -kv[1])[:n]]


def idle_gaps(trace: DeviceTrace, n: int = 10) -> List[List]:
    """Idle time of the first device grouped by what the host was doing:
    each gap between its ops is named by the shortest host event covering
    the gap's middle (the first of those, in order of start, where several
    are as short; the client's annotations, the runtime's events), and the
    ``n`` names with the most idle seconds are returned.  One sweep over
    the gaps in time order: host events join a heap by duration as they
    start and leave it once ended, so a trace of a million host events
    reduces in seconds."""
    if not trace.ops:
        return []
    evs = sorted(trace.ops[trace.devices[0]], key=lambda e: e.start_ns)
    host = sorted(trace.host, key=lambda h: h.start_ns)
    gaps: List[Tuple[float, float]] = []     # (middle, idle ns), in order
    end = None
    for ev in evs:
        if end is not None and ev.start_ns > end:
            gaps.append(((end + ev.start_ns) / 2, ev.start_ns - end))
        end = ev.end_ns if end is None else max(end, ev.end_ns)
    by_label: Dict[str, float] = {}
    live: List[Tuple[float, int]] = []            # (duration, host index)
    nxt = 0
    for mid, idle in gaps:
        while nxt < len(host) and host[nxt].start_ns <= mid:
            heapq.heappush(live, (host[nxt].dur_ns, nxt))
            nxt += 1
        while live and host[live[0][1]].end_ns < mid:
            heapq.heappop(live)
        label = host[live[0][1]].name if live else "no host event"
        by_label[label] = by_label.get(label, 0.0) + idle
    top = sorted(by_label.items(), key=lambda kv: -kv[1])[:n]
    return [[label, ns / 1e9] for label, ns in top]
