"""The whole step's share of the chip's peak: FLOPs of the requests served
(``flops.request_flops``; padding rows not counted) over the summed wall
time of the server's ``batch/*`` dispatch spans, times the chips, times the
bf16 peak, in percent."""


def read(ctx):
    secs = ctx.dispatch_s()
    work = ctx.served_flops()
    if secs <= 0 or work <= 0 or not ctx.peak:
        return None
    return 100.0 * work / (secs * ctx.chips * ctx.bf16_peak)
