"""Device: the share of the traced window in which no operation ran,
averaged over the devices the cell uses."""


def read(ctx):
    tr = ctx.trace
    if tr is None or not tr.ops or tr.window_s <= 0:
        return None
    busy = ctx.tracing.busy_s(tr)
    return None if busy <= 0 else 1.0 - busy / tr.window_s
