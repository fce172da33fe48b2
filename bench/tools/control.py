#!/usr/bin/env python3
"""Readings that set a cell's limits, on the chip at the cell's own size.

    python3 bench/tools/control.py --workload <name> --seeds 1,2,3 \
        --faults none,control_fp8,half_batch [--seconds 1]

Every run goes through the cell's own driver, window and comparison, with
the named fault or control planted under its timed path
(``bench/faults.py``; ``none`` plants nothing), all in one process.
Training's readings need no long window: one second runs one job in it.
Prints one JSON object per run: the seed, the fault, ``correct`` and
every compared number.
"""

from __future__ import annotations

import argparse
import contextlib
import gc
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for p in (os.path.join(ROOT, "src"), ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)


def _ints(s: str):
    return [int(x) for x in s.split(",") if x]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=_ints, required=True)
    ap.add_argument("--faults", required=True)
    ap.add_argument("--seconds", type=float, default=1.0)
    args = ap.parse_args(argv)
    import jax
    from bench import faults, harness
    from bench.record import Record
    if jax.devices()[0].platform != "tpu":
        print("control: no TPU", file=sys.stderr)
        return 2
    harness.enable_cache(ROOT)
    cell = harness.find_cell(harness.load_benchmark(ROOT), args.workload)
    planted = faults.BY_DRIVER[cell.config["driver"]]
    drv = harness.driver(cell.config)
    for name in args.faults.split(","):
        for seed in args.seeds:
            t = time.perf_counter()
            rec = Record(t, trace=False,
                         log=lambda s: print(s, file=sys.stderr, flush=True))
            fault = (contextlib.nullcontext() if name == "none"
                     else planted[name](cell))
            with fault:
                drv.run(cell, seed, args.seconds, rec, jax.devices()[:1])
            print(json.dumps({
                "seed": seed, "fault": name, "correct": rec.correct,
                "checks": {n: v for n, v, _ in rec.checks},
                "seconds": time.perf_counter() - t}), flush=True)
            del rec
            gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
