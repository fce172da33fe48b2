"""Benchmark harness — one module per paper table/claim.

Prints ``name,us_per_call,derived`` CSV:

  bench_message_complexity  §9 tables (counter / OR-Set / MVR, + protocol
                            bytes per shipping policy)
  bench_antientropy         Algorithm 1 vs Algorithm 2 under loss, plus
                            bytes-shipped per shipping policy under
                            loss/dup/partition
  bench_tensor_sync         tensor-lattice delta shipping + join throughput
  bench_kernels             kernel microbenchmarks (CPU proxies)
  bench_store               keyed LatticeStore: batched vs per-key join
                            throughput + sharded bytes-per-round scaling
  bench_wire                binary δ-wire codec: sparse-round frame bytes
                            vs dense full-state encoding, rebalance
                            handoff vs organic anti-entropy, digest-sync
                            reconnect catch-up vs the full-state fallback,
                            per-group zlib column compression
  bench_lifecycle           key lifecycle: resident bytes return to
                            ~baseline after TTL + acked reap, straggler
                            replays never resurrect, read-replica
                            hot-key convergence outside the write set
  bench_dots                columnar dot-store fast path: 1M-dot causal
                            join vs the frozenset oracle (>=10x,
                            bit-identical), per-dot digest reconnect
                            bytes vs full state (<=5%), add_dots
                            contiguous-append fast path
  bench_net                 real loopback sockets: UDP load generator
                            (throughput + p50/p99 convergence latency
                            under 10% loss), TCP kill/restart digest-sync
                            catch-up (<=25% of full state), 3-process
                            serve.py cluster fingerprint agreement
  bench_topology            3-zone hierarchical gossip vs flat mesh:
                            cross-zone (WAN) bytes strictly beat the
                            mesh at equal workload in sim AND over real
                            loopback sockets; zone partition heals with
                            no write lost
  bench_obs                 observability: tracing overhead within 10%
                            of untraced throughput, 3-process
                            serve.py --metrics cluster scraped over
                            sidecar HTTP, trace-analyzer redundancy +
                            convergence rollup with zero anomalies
  bench_roofline            per-(arch × shape × mesh) roofline rows from
                            the dry-run artifacts (run dryrun first)

``--json [out.json]`` additionally writes a machine-readable artifact
(name → {us_per_call, derived}, stamped with the git revision and
per-suite wall times, kernel-launch counts, and — per suite — the obs
registry snapshot the suite populated: marker replication lags, queue
drops, redundancy-ratio gauges) so the perf trajectory is
recorded per-commit; a
bare ``--json`` writes ``BENCH_tier1.json`` in the current directory,
which is the repo root in CI (the workflow uploads it). ``--only a,b``
restricts to a subset of suites.
"""

from __future__ import annotations

import argparse
import json
import math
import sys
import time


def _git_revision() -> str:
    """The current commit hash (stamps the JSON artifact so per-commit
    perf trajectories can be reconstructed); 'unknown' outside a repo."""
    import subprocess
    try:
        out = subprocess.run(["git", "rev-parse", "HEAD"],
                             capture_output=True, text=True, timeout=10)
        return out.stdout.strip() or "unknown"
    except Exception:
        return "unknown"


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--json", nargs="?", const="BENCH_tier1.json",
                    default=None, metavar="OUT.json",
                    help="also write results as machine-readable JSON "
                         "(bare --json writes BENCH_tier1.json in the "
                         "current directory — the repo root in CI)")
    ap.add_argument("--only", default=None,
                    help="comma-separated suite names (default: all)")
    args = ap.parse_args(argv)
    if args.json:
        import os
        out_dir = os.path.dirname(os.path.abspath(args.json))
        if not os.path.isdir(out_dir):
            ap.error(f"--json: directory {out_dir} does not exist")

    from . import (bench_antientropy, bench_dots, bench_kernels,
                   bench_lifecycle, bench_message_complexity, bench_net,
                   bench_obs, bench_roofline, bench_store,
                   bench_tensor_sync, bench_topology, bench_wire)

    modules = [
        ("message_complexity", bench_message_complexity),
        ("antientropy", bench_antientropy),
        ("tensor_sync", bench_tensor_sync),
        ("kernels", bench_kernels),
        ("store", bench_store),
        ("wire", bench_wire),
        ("lifecycle", bench_lifecycle),
        ("dots", bench_dots),
        ("topology", bench_topology),
        ("net", bench_net),
        ("obs", bench_obs),
        ("roofline", bench_roofline),
    ]
    if args.only:
        keep = {s.strip() for s in args.only.split(",")}
        unknown = keep - {n for n, _ in modules}
        if unknown:
            raise SystemExit(f"unknown suites {sorted(unknown)}; "
                             f"have {[n for n, _ in modules]}")
        modules = [(n, m) for n, m in modules if n in keep]

    from repro.kernels.ops import counters as _kernel_counters
    from repro.obs import reset_global_registry as _reset_registry

    print("name,us_per_call,derived")
    results = {}
    suite_wall = {}
    suite_launches = {}
    suite_metrics = {}
    failures = 0
    run_t0 = time.perf_counter()
    for name, mod in modules:
        t0 = time.perf_counter()
        snap = _kernel_counters.snapshot()
        # each suite gets a fresh process-wide registry, so its snapshot
        # (marker lags, queue drops, redundancy gauges) is per-suite
        reg = _reset_registry()
        try:
            rows = mod.run()
        except Exception as e:  # report, keep going
            failures += 1
            print(f"{name}_FAILED,nan,{type(e).__name__}: {e}")
            results[f"{name}_FAILED"] = {
                "us_per_call": None, "derived": f"{type(e).__name__}: {e}"}
            continue
        for row_name, us, derived in rows:
            print(f"{row_name},{us:.1f},{derived}")
            results[row_name] = {
                "us_per_call": None if math.isnan(us) else us,
                "derived": derived,
            }
        dt = time.perf_counter() - t0
        suite_wall[name] = round(dt, 3)
        suite_launches[name] = _kernel_counters.since(snap)["launches"]
        metrics = json.loads(reg.render_json())   # NaN/Inf cleaned
        if metrics:
            suite_metrics[name] = metrics
        print(f"# {name} done in {dt:.1f}s", file=sys.stderr)
    if args.json:
        with open(args.json, "w") as f:
            json.dump({"git_revision": _git_revision(),
                       "wall_time_s": round(time.perf_counter() - run_t0, 3),
                       "suite_wall_s": suite_wall,
                       "suite_launch_count": suite_launches,
                       "suite_metrics": suite_metrics,
                       "suites": [n for n, _ in modules],
                       "failures": failures,
                       "results": results}, f, indent=1, allow_nan=False)
        print(f"# wrote {args.json} ({len(results)} rows)", file=sys.stderr)
    if failures:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
