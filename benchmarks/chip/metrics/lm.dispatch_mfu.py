"""LM serving: the whole step's share of the chip's peak.  The FLOPs of FP
plus input-gradient BP over the live tokens of every prompt the window
answered (the kind's ``flops.explain``, from each prompt's length in the
plan; padding not counted) over the summed wall time of the server's
``batch/*`` dispatch spans, times the chips, times the bf16 peak, in
percent.  Read only from a program whose token launches count their
tokens (``tokens_live`` on the launch spans)."""


def read(ctx):
    if not any(s.name == "engine.attribute" and "tokens_live" in s.args
               for s in ctx.spans):
        return None
    secs = ctx.dispatch_s()
    plan = ctx.window.plan
    work = sum(ctx.flops.explain(ctx.model,
                                 int((plan.payload(r.session) != 0).sum()))
               for r in ctx.window.records("explain") if r.ok)
    if secs <= 0 or work <= 0 or not ctx.peak:
        return None
    return 100.0 * work / (secs * ctx.chips * ctx.bf16_peak)
