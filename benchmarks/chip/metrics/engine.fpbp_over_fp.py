"""Engine (``engine/engine.py`` forward and replay programs): the paper's
Table IV on the device.  (forward + replay device time per launched row)
over forward device time per row: a program execution is a forward when it
ran a conv forward kernel and a replay when it ran a fused conv backward;
rows come from the launches rebuilt from the server's spans."""

import importlib.util
from pathlib import Path


def _patterns(metric):
    """The kernel signatures the roofline reader of ``metric`` matches."""
    path = Path(__file__).with_name(f"{metric}.py")
    spec = importlib.util.spec_from_file_location(f"_{path.stem}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.PATTERNS


FORWARD = _patterns("kernels.conv_fwd_roofline")
REPLAY = _patterns("kernels.conv_bwd_roofline")


def read(ctx):
    if ctx.trace is None:
        return None
    fwd_s, _ = ctx.tracing.modules_by_kernels(ctx.trace, FORWARD)
    bwd_s, _ = ctx.tracing.modules_by_kernels(ctx.trace, REPLAY)
    fwd_rows = sum(lc.rows for lc in ctx.launches if lc.program == "forward")
    bwd_rows = sum(lc.rows for lc in ctx.launches if lc.program == "replay")
    if min(fwd_s, bwd_s) <= 0 or not fwd_rows or not bwd_rows:
        return None
    fp = fwd_s / fwd_rows
    return (fp + bwd_s / bwd_rows) / fp
