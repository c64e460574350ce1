# One function per paper table. Prints ``name,us_per_call,derived`` CSV and
# writes ``benchmarks/results/BENCH_<UTC-date>.json`` (suite -> rows) so the
# perf trajectory stays machine-readable across PRs.
#
#   memory_overhead      — paper Table II + §V (3.4 Mb -> 24.7 Kb, 137x)
#   fp_bp_overhead       — paper Table IV (FP vs FP+BP latency, 50-72%)
#   kernels              — paper §III compute blocks (conv/VMM/ReLU/pool)
#   attribution_serving  — 'real-time XAI' at LM scale (decode vs explain)
#   lm_attribution       — repro.lm: per-generated-token attribution cost
#   serving_queue        — repro.serve queue: p50/p99, cache hits, occupancy
#   load_replay          — O(100k)-request SLO replay: p99/shed-rate gates
#   perturbation         — folded perturb forward vs lax.map; rise fan-out
#   roofline             — §Roofline terms from the dry-run artifacts
from __future__ import annotations

import datetime
import json
import math
import os
import traceback


def _row_val(val):
    """Snapshot cell: finite float or None (strict JSON; NaN is a bug)."""
    if val is None:
        return None
    v = float(val)
    return v if math.isfinite(v) else None


def main() -> None:
    from repro.launch import compile_cache
    compile_cache.enable()
    from benchmarks import (attribution_serving, compression, fp_bp_overhead,
                            kernels, lm_attribution, load_replay,
                            memory_overhead, perturbation, roofline,
                            serving_queue)
    suites = [
        ("memory_overhead", memory_overhead.run),
        ("fp_bp_overhead", fp_bp_overhead.run),
        ("kernels", kernels.run),
        ("attribution_serving", attribution_serving.run),
        ("lm_attribution", lm_attribution.run),
        ("serving_queue", serving_queue.run),
        ("load_replay", load_replay.run_bench),
        ("perturbation", perturbation.run),
        ("compression", compression.run),
        ("roofline", roofline.run),
    ]
    results, failures = {}, []
    for name, fn in suites:
        try:
            rows = [(row, _row_val(val), derived)
                    for row, val, derived in fn()]
            results[name] = rows
            for row, val, derived in rows:
                v = f"{val:.3f}" if val is not None else "-"
                print(f"{row},{v},{derived}", flush=True)
        except Exception:
            failures.append(name)
            print(f"{name},nan,FAILED", flush=True)
            traceback.print_exc()

    date = datetime.datetime.now(datetime.timezone.utc).strftime("%Y-%m-%d")
    out_dir = os.path.join(os.path.dirname(__file__), "results")
    os.makedirs(out_dir, exist_ok=True)
    out_path = os.path.join(out_dir, f"BENCH_{date}.json")
    with open(out_path, "w") as f:
        # strict JSON: _row_val already mapped non-finite cells to None,
        # allow_nan=False makes any future NaN a loud failure here
        json.dump({"date": date, "suites": results, "failures": failures},
                  f, indent=1, allow_nan=False)
    print(f"[bench] wrote {out_path}", flush=True)

    if failures:
        raise SystemExit(f"{len(failures)} benchmark suites failed")


if __name__ == "__main__":
    main()
