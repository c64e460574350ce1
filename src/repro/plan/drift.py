"""Cost-model drift: measured kernel times vs :meth:`Footprint.est_time_s`.

The planner ranks tile candidates by the analytic roofline in
:mod:`repro.plan.model`; the autotuner records what the kernels actually
cost.  This module joins the two into one table — per
``cnn_kernel_shapes`` launch, the estimated and measured microseconds and
their ratio — so a drifting cost model is a number you can watch, not a
vibe.

Measured times come from the first available source per row:

  1. the tuning cache's ``measured_us`` (written by ``autotune=True``
     plans);
  2. a fresh eager :func:`repro.plan.planner.measure_kernel` calibration
     when ``measure=True`` (pool launches carry no tile knob and are not
     measurable this way).

The table persists next to the tuning cache (``<cache>.drift.json``) as
strict JSON, and ``python -m repro.obs drift`` prints it.  Kernel times of
served launches are in the device trace, where every Pallas kernel is
named after its family.
"""
from __future__ import annotations

import os
from typing import Any, Dict, List, Optional

from repro.obs import jsonsafe
from repro.plan.cache import TuningCache, cache_key, default_cache_path
from repro.plan.planner import (PLAN_DTYPES, TilePlan, _footprint,
                                _plan_family, cnn_kernel_shapes,
                                measure_kernel)
from repro.plan.profiles import get_profile

__all__ = ["drift_path", "drift_rows", "format_drift", "write_drift"]


def drift_path(cache_path: Optional[str] = None) -> str:
    """Drift-table path next to the tuning cache it calibrates."""
    base = cache_path if cache_path is not None else default_cache_path()
    root, _ = os.path.splitext(base)
    return root + ".drift.json"


def _measured_us(family, kw, dims, precision, tile, profile, *,
                 cache=None, measure=False):
    """(measured_us, source) from the first source that has this launch."""
    if cache is not None and family != "pool":
        ck = cache_key(family, list(dims), PLAN_DTYPES[precision],
                       precision, profile.name)
        entry = cache.lookup(ck, require_measured=True)
        if entry is not None:
            return entry["measured_us"], "cache"
    if measure and family != "pool":
        if tile is None:
            tile, _ = _plan_family(family, kw, profile, precision, False)
        return measure_kernel(family, kw, tile, precision), "measured"
    return None, None


def drift_rows(cfg, plan: Optional[TilePlan] = None, *, device=None,
               precision: str = "f32", batch: int = 1, seeds: int = 1,
               cache: Optional[TuningCache] = None,
               measure: bool = False) -> List[Dict[str, Any]]:
    """One row per CNN kernel launch: est_us, measured_us, drift ratio.

    Rows without any measured source carry ``measured_us=None`` and
    ``drift=None`` (strict-JSON safe) so the table always names every
    launch even before calibration.
    """
    profile = get_profile(device if device is not None
                          else (plan.device if plan is not None else None))
    rows = []
    for key, family, kw in cnn_kernel_shapes(cfg, batch, seeds):
        tile = plan.get(key) if plan is not None else None
        est_s = _footprint(family, kw, tile, precision,
                           profile).est_time_s(profile)
        dims = tuple(int(v) for v in kw.values())
        measured, source = _measured_us(
            family, kw, dims, precision, tile, profile,
            cache=cache, measure=measure)
        est_us = 1e6 * est_s
        rows.append({
            "key": key, "family": family,
            "shape": "x".join(str(d) for d in dims),
            "precision": precision, "device": profile.name,
            "est_us": est_us,
            "measured_us": measured,
            "source": source,
            "drift": (measured / est_us
                      if measured is not None and est_us > 0 else None),
        })
    return rows


def write_drift(rows: List[Dict[str, Any]],
                path: Optional[str] = None) -> str:
    """Persist the table (strict JSON) next to the tuning cache."""
    out = path if path is not None else drift_path()
    d = os.path.dirname(out)
    if d:
        os.makedirs(d, exist_ok=True)
    with open(out, "w") as f:
        jsonsafe.dump_strict({"rows": rows}, f, indent=2)
    return out


def format_drift(rows: List[Dict[str, Any]]) -> str:
    """Fixed-width table; unmeasured rows print '-'."""
    hdr = (f"{'key':<12} {'family':<11} {'shape':<24} "
           f"{'est_us':>10} {'meas_us':>10} {'drift':>7}  source")
    lines = [hdr, "-" * len(hdr)]
    for r in rows:
        meas = f"{r['measured_us']:.1f}" if r["measured_us"] is not None \
            else "-"
        drift = f"{r['drift']:.2f}x" if r["drift"] is not None else "-"
        lines.append(f"{r['key']:<12} {r['family']:<11} {r['shape']:<24} "
                     f"{r['est_us']:>10.1f} {meas:>10} {drift:>7}  "
                     f"{r['source'] or '-'}")
    return "\n".join(lines)
