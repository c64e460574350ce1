#!/usr/bin/env python3
"""Run one cell of the chip benchmark once.

    python3 benchmarks/chip/run.py --workload <name> --seed <n> \
        --seconds <s> --trace <0|1>

from the root of a checkout, on a machine that holds the chips the cell
asks for.  One process: it refuses to run without a TPU, with fewer chips
than the cell needs, or on a device kind missing from ``peaks.json``;
makes the weights on the device from the configuration's weight seed;
warms every shape the cell's traffic launches (set-up, ``setup_s``); drives
the traffic for ``--seconds`` through ``ExplanationServer.submit`` and
``.poll``; drains; compares every answer with the plain reference; and
prints one JSON line as the last line of standard output::

    {"correct": ..., "attempted": ..., "failed": ..., "metrics": {...},
     "device": {...}, "breakdown": {...}, "checks": {...}}

``--trace 0`` reports the cell's end-to-end metrics, ``--trace 1`` its
per-layer metrics from a profiler trace of the window and the server's
spans.  ``--seed`` draws the payloads (through the configuration's model
kind, ``kinds/<kind>.py``: images for the CNN); the mix fixes the timing
skeleton (arrivals, think times, methods and panels), the same for every
seed.
The numbers compared, each beside its limit, are also the last lines of
standard error.
"""
from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

import chipbench  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--dump", type=Path, default=None,
                    help="write a summary of the trace here (--trace 1)")
    args = ap.parse_args(argv)
    from chipbench import bench, cell as cell_lib, drive
    try:
        result = bench.run(args.workload, args.seed, args.seconds,
                           bool(args.trace), t_start=T_START,
                           dump_dir=args.dump)
    except cell_lib.RefusedError as e:
        bench.log(f"refused: {e}")
        return 2
    except drive.SetupError as e:
        bench.log(f"set-up failed: {e}")
        return 1
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    chipbench.pin_compile_cache()
    sys.exit(main())
