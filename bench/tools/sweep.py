#!/usr/bin/env python3
"""Find the highest rate a store cell sustains: one set-up, then one
window at each rate, in one process on the chip.

    python3 bench/tools/sweep.py --workload ycsb_a-3rep --seed 1 \
        --rates 1,2,4 --seconds 20

For each rate it prints the service loop's lateness (how late operations
started against their due time) in the first and the last quarter of the
window: below the knee it does not grow. Latency tails are printed
beside it.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for p in (os.path.join(ROOT, "src"), ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    args = ap.parse_args(argv)
    import jax
    from bench import harness, workgen
    from bench.drivers import store
    from bench.record import Record
    if jax.devices()[0].platform != "tpu":
        print("sweep: no TPU", file=sys.stderr)
        return 2
    harness.enable_cache(ROOT)
    cell = harness.find_cell(harness.load_benchmark(ROOT), args.workload)
    log = lambda s: print(s, file=sys.stderr, flush=True)
    cluster = store.prepare(cell, args.seed,
                            Record(time.perf_counter(), False, log))
    for n, rate in enumerate(float(r) for r in args.rates.split(",")):
        rec = Record(time.perf_counter(), False, log)
        cluster.rec = rec
        traffic = dict(cell.traffic, rate_ops_s=rate)
        ops = workgen.open_loop(traffic, cluster.n_rows, len(cluster.reps),
                                cluster.width, args.seed, args.seconds,
                                stream=100 + n)
        store.measure(cluster, ops, args.seconds, 30.0)
        lat = rec.samples["lateness_ms"]
        print(json.dumps({
            "rate_ops_s": rate, "ops": len(ops),
            "lateness_quarters_ms": store._quarters(lat),
            "update_visible_p95_ms": harness.percentile(
                rec.samples["update_visible_ms"], 95),
            "read_p95_ms": harness.percentile(rec.samples["read_ms"], 95),
            "never_visible": rec.failed,
            "put_ms": 1e3 * sum(rec.span_seconds("put"))
            / max(len(rec.spans["put"]), 1),
            "tick_ms": 1e3 * sum(rec.span_seconds("tick"))
            / max(len(rec.spans["tick"]), 1),
            "ticks": len(rec.spans["tick"])}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
