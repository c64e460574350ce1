"""Front end (``ExplanationServer.submit``): the median time a submit call
takes, on the client's clock around each call, in microseconds."""
from chipbench.stats import percentile


def read(ctx):
    vals = sorted(r.submit_s for r in ctx.window.records())
    v = percentile(vals, 50)
    return None if v is None else 1e6 * v
