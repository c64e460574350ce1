"""Batcher (``serve/batcher``): the 95th percentile of the time requests
wait in the batcher, from ``submit`` to the start of their launch (the
program's ``queued`` spans), in milliseconds.  It is the tail of the
request latency, which spreads too widely from run to run on one chip to
hold a bound as an end-to-end metric."""
from chipbench.stats import percentile


def read(ctx):
    waits = sorted(s.duration for s in ctx.spans
                   if s.name == "queued" and s.duration is not None)
    v = percentile(waits, 95)
    return None if v is None else 1e3 * v
