"""LM engine: the share of the device's busy time spent in the selective
scan, its forward kernel (``kernels.ssm_scan_roofline``'s executions) and
its backward kernel (``pallas_call(name="ssm_scan_bwd")``), over the time
in which any operation ran."""
import importlib.util
from pathlib import Path

_path = Path(__file__).with_name("kernels.ssm_scan_roofline.py")
_spec = importlib.util.spec_from_file_location("_ssm_scan_roofline", _path)
_mod = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_mod)
FORWARD = _mod.PATTERNS
BACKWARD = (r"%ssm_scan_bwd(?:\.[\w.\-]+)? = [^\n]*custom_call_target="
            r"\"tpu_custom_call\"",)


def read(ctx):
    tr = ctx.trace
    if tr is None or not tr.ops:
        return None
    scan_s = (ctx.tracing.family_seconds(tr, FORWARD)
              + ctx.tracing.family_seconds(tr, BACKWARD))
    busy = ctx.tracing.busy_s(tr) * len(tr.ops)
    if scan_s <= 0 or busy <= 0:
        return None
    return scan_s / busy
