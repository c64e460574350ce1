"""Dispatch (``ExplanationServer._dispatch``): the share of launch time in
which the serving thread ran on no CPU, over the program's ``batch/*``
spans: the sum of (duration - ``cpu_s``) over the sum of durations.  The
thread is then blocked on the device, the runtime or the GIL, or waits on
a run queue (``runq_s``).  A program whose spans carry no ``cpu_s`` leaves
the metric nothing to read."""


def read(ctx):
    spans = [s for s in ctx.spans if s.cat == "batch"
             and s.duration is not None
             and (s.args or {}).get("cpu_s") is not None]
    wall = sum(s.duration for s in spans)
    if wall <= 0:
        return None
    return sum(s.duration - s.args["cpu_s"] for s in spans) / wall
