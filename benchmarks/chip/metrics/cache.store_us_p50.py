"""Residual cache (``serve/residual_cache``): the median time a launch
takes to store its rows in the cache (the program's ``cache.store`` phase
spans: one eager slice per residual leaf per request, then ``put``), in
microseconds.  A program without phase spans leaves the metric nothing to
read."""
from chipbench.stats import percentile


def read(ctx):
    v = percentile(sorted(s.duration for s in ctx.spans
                          if s.name == "cache.store" and s.cat == "phase"
                          and s.duration is not None), 50)
    return None if v is None else 1e6 * v
