"""Kernels (``kernels/ssm_scan``): the selective scan's forward kernel,
its share of its roofline, in percent: the least time the peaks allow for
the scans its executions ran (the kind's ``flops.scan_forward``: every
Mamba layer of every token launch, at the launch's rows and padded length)
over the summed device time of its executions.  The attribution step runs
each layer's forward scan once going forward and once more as the layer is
recomputed going back; the count of executions per layer and launch is
read from the trace.  The scan is VPU work, which no published peak
covers, so the bound held against it is HBM bandwidth (and the bf16 peak
for its operations, which never binds)."""

#: the scan's Pallas call (``pallas_call(name="ssm_scan")``): an HLO
#: instruction named after the kernel, a Mosaic custom call
PATTERNS = (r"%ssm_scan(?:\.[\w.\-]+)? = [^\n]*custom_call_target="
            r"\"tpu_custom_call\"",)


def read(ctx):
    if ctx.trace is None:
        return None
    events = ctx.tracing.matching(ctx.tracing.all_ops(ctx.trace), PATTERNS)
    launches = [s for s in ctx.spans
                if s.name == "engine.attribute" and "tokens_padded" in s.args]
    layers = ctx.flops.mamba_layers(ctx.model)
    if not events or not launches or not layers:
        return None
    per_layer = round(len(events) / (len(launches) * layers))
    if per_layer < 1:
        return None
    need = 0.0
    for s in launches:
        rows = s.args["rows"]
        ops, nbytes = ctx.flops.scan_forward(
            ctx.model, rows, s.args["tokens_padded"] // rows)
        need += per_layer * layers * max(ops / ctx.bf16_peak,
                                         nbytes / ctx.hbm_bw)
    dev_s = sum(e.dur_ns for e in events) / 1e9
    return None if dev_s <= 0 else 100.0 * need / dev_s
