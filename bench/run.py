#!/usr/bin/env python3
"""Run one benchmark cell once on the chip and print its result.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell (workload, configuration, traffic and metrics) is looked up by
name in ``BENCHMARK.json``. Set-up makes the data and weights from
``--seed``, warms every shape the window will use, then the window
measures for ``--seconds``; afterwards the run checks what the timed path
produced against a plain reference. The last line on standard output is
one JSON object: ``correct``, ``attempted``, ``failed``, ``metrics``,
``device`` (and ``breakdown`` with ``--trace 1``), then ``checks``, each
compared number with its limit. The same numbers are the last lines on
standard error.

It runs only on a TPU: on any other platform, or with fewer chips than
the cell asks for, it exits non-zero and prints no result. JAX's
persistent compilation cache is ``.jax_cache/`` at the root of the
checkout.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def device_line(devices, rec) -> dict:
    d = devices[0]
    out = {"platform": d.platform, "kind": d.device_kind,
           "count": len(devices),
           "memory_peak_bytes": rec.memory_peak_bytes}
    if rec.trace_summary is not None:
        out["busy_s"] = rec.trace_summary.busy_s
        out["window_s"] = rec.trace_summary.window_s
    return out


def main(argv=None) -> int:
    args = parse_args(argv)
    for p in (os.path.join(ROOT, "src"), ROOT):
        if p not in sys.path:
            sys.path.insert(0, p)
    from bench import harness
    cell = harness.find_cell(harness.load_benchmark(ROOT), args.workload,
                             ROOT)

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        _log(f"bench: no TPU (JAX reports platform "
             f"{devices[0].platform!r}); the benchmark runs only on the chip")
        return 2
    chips = int(cell.workload["chips"])
    if len(devices) < chips:
        _log(f"bench: {args.workload} needs {chips} chips, JAX sees "
             f"{len(devices)}")
        return 2
    devices = devices[:chips]
    from bench.peaks import peaks
    peak = peaks(devices[0].device_kind)
    _log(f"bench: {args.workload} on {devices[0].device_kind} x{chips}; "
         f"compile cache {harness.enable_cache(ROOT)}")

    from bench.record import Record
    rec = Record(T_START, trace=bool(args.trace), log=_log)
    drv = harness.driver(cell.config, ROOT)
    ctx = drv.run(cell, args.seed, args.seconds, rec, devices)
    ctx.update(peaks=peak, config=cell.config, traffic=cell.traffic)
    line = harness.result_line(cell, rec, ctx, device_line(devices, rec),
                               ROOT)
    _log(f"bench: setup_s {rec.setup_s:.3f}, window_s {rec.window_s:.3f}, "
         f"compiles in window {int(rec.counts['compiles_in_window'])}")
    for name, value, limit in rec.checks:
        _log(f"check {name} = {value!r} (limit {limit!r})")
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
