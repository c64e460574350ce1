#!/usr/bin/env python3
"""Find the knee of an open-loop cell: the highest offered rate its server
sustains without a growing backlog, in one process.

    python3 benchmarks/chip/sweep.py --workload <name> --rates 20,40,80 \
        --seconds <s> --seed <n> [--out DIR]

Prepares the cell once and runs one window per rate, overriding only the
mix's ``rate_per_s``.  Per rate it prints one JSON line: requests sent and
failed, explain and predict latency percentiles, how late the generator
ran, how long the server took to drain after the close, and the median
explain latency of the window's last third over its first third (above
about 1.5 the backlog grows).  The chosen rate is written into the traffic
file by hand, with the table in PERF.md.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))

import chipbench  # noqa: E402

chipbench.pin_compile_cache()

from chipbench import bench, drive  # noqa: E402
from chipbench.stats import percentile  # noqa: E402


def _ms(vals, q):
    v = percentile(sorted(vals), q)
    return None if v is None else round(1e3 * v, 4)


def row(m: bench.Measured, rate: float, seconds: float) -> dict:
    w = m.window
    recs = w.records()
    ex = sorted(w.records(drive.EXPLAIN), key=lambda r: r.due)
    third = max(len(ex) // 3, 1)
    first = [r.latency_s for r in ex[:third]]
    last = [r.latency_s for r in ex[-third:]]
    growth = (percentile(sorted(last), 50) / percentile(sorted(first), 50)
              if first and last else None)
    return {
        "rate_per_s": rate, "sent": len(recs),
        "failed": sum(not r.ok for r in recs),
        "explains_done_per_s": sum(r.ok and r.done_t <= w.end
                                   for r in ex) / seconds,
        "explain_p50_ms": _ms([r.latency_s for r in ex], 50),
        "explain_p95_ms": _ms([r.latency_s for r in ex], 95),
        "predict_p95_ms": _ms([r.latency_s
                               for r in w.records(drive.PREDICT)], 95),
        "late_p99_ms": _ms([r.late_s for r in recs], 99),
        "drain_s": round(w.closed_t - w.end, 4),
        "last_over_first_p50": growth,
        "occupancy": (m.server.stats.batched_rows
                      / max(m.server.stats.padded_rows, 1)),
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--out", type=Path, default=None)
    args = ap.parse_args(argv)
    prep = bench.prepare(args.workload)
    bench.warm(prep, prep.plan(args.seed, args.seconds))
    rows = []
    for rate in (float(r) for r in args.rates.split(",")):
        mix = dict(prep.cell.mix, rate_per_s=rate)
        plan = prep.plan(args.seed, args.seconds, mix)
        m = bench.measure(prep, plan, args.seconds, trace=False)
        r = dict(row(m, rate, args.seconds), workload=args.workload,
                 lowered=m.lowered)
        rows.append(r)
        print(json.dumps(r), flush=True)
    if args.out is not None:
        args.out.mkdir(parents=True, exist_ok=True)
        with open(args.out / f"{args.workload}.sweep.jsonl", "a") as f:
            for r in rows:
                f.write(json.dumps(r) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
