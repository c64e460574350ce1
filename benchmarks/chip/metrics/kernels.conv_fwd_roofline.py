"""Kernels (``kernels/conv2d``, f32 and fxp): the conv forward dots' share of
their roofline, in percent: the least time ``flops.py`` and the peaks allow
for every conv layer of every forward launch, over the summed device time
of the ops that ran those kernels."""

#: The trace names an op by its HLO instruction only; the Pallas kernel's
#: function name is not in it.  A conv forward dot is the one Mosaic custom
#: call whose result and both operands (the halo-padded image, the weights)
#: are rank 4, whatever their element type.
_L = r"\{[^}]*\}"
_R4 = r"\w+\[\d+(?:,\d+){3}\]" + _L
PATTERNS = (r"= " + _R4 + r" custom-call\(" + _R4 + r" %[\w.\-]+, " + _R4
            + r" %[\w.\-]+\), custom_call_target=\"tpu_custom_call\"",)


def read(ctx):
    if ctx.trace is None:
        return None
    dev_s = ctx.tracing.family_seconds(ctx.trace, PATTERNS)
    if dev_s <= 0:
        return None
    f = ctx.flops
    need = 0.0
    for lc in ctx.launches:
        if lc.program != "forward":
            continue
        rows = ctx.rows_per_shard(lc.rows)
        for lyr in f.layers(ctx.model):
            if lyr.kind == "conv":
                w = f.conv_forward_launch(lyr, rows, ctx.precision)
                need += ctx.shards * f.roofline_s(
                    w["flops"], w["bytes"], ctx.bf16_peak, ctx.hbm_bw)
    if need <= 0:
        return None
    return 100.0 * need / dev_s
