#!/usr/bin/env python3
"""Readings the limits of ``correct`` are set from, in one process.

    python3 benchmarks/chip/calibrate.py --workload <name> \
        --seeds 1001,1002,... --control-seeds 3 --seconds <s> [--out DIR]

Prepares the cell once, then for each seed runs a window at the cell's own
load and compares every answer with the reference, as ``run.py`` does (the
program's readings).  For the first ``--control-seeds`` seeds it also puts
the control in the program's place: the reference computed in the
precision below the configuration's (``"control"`` in its file), answering
the same requests, compared the same way.  Prints one JSON line per
reading; the benchmark's own runs never run the control.
"""
from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parent))

import chipbench  # noqa: E402

chipbench.pin_compile_cache()

from chipbench import bench  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated traffic seeds")
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out", type=Path, default=None)
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",")]
    prep = bench.prepare(args.workload)
    bench.warm(prep, prep.plan(seeds[0], args.seconds))
    bench.log(f"set-up {time.monotonic() - T_START:.3f} s")
    lines = []
    for i, seed in enumerate(seeds):
        plan = prep.plan(seed, args.seconds)
        m = bench.measure(prep, plan, args.seconds, trace=False)
        failed = sum(not r.ok for r in m.window.records())
        e2e = bench.end_to_end(prep.cell, m.window, 0.0)
        served = bench.served_answers(prep, m, plan)
        del m
        gc.collect()
        row = {"workload": args.workload, "seed": seed, "who": "program",
               "answers": len(served), "failed": failed,
               "readings": prep.kind.numbers(prep.params, prep.model,
                                             served),
               "end_to_end": {k: v["value"] for k, v in e2e.items()}}
        lines.append(row)
        print(json.dumps(row), flush=True)
        if i < args.control_seeds:
            mode = prep.cell.config["control"]
            ctrl = prep.kind.control_answers(prep.params, prep.model,
                                             served, mode)
            row = {"workload": args.workload, "seed": seed,
                   "who": f"control:{mode}", "answers": len(ctrl),
                   "readings": prep.kind.numbers(prep.params, prep.model,
                                                 ctrl)}
            lines.append(row)
            print(json.dumps(row), flush=True)
    if args.out is not None:
        args.out.mkdir(parents=True, exist_ok=True)
        with open(args.out / f"{args.workload}.calibrate.jsonl", "a") as f:
            for row in lines:
                f.write(json.dumps(row) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
