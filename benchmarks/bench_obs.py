"""Observability benchmark: tracing overhead, live scrape, trace rollup.

Three rows, each asserting the claim it measures:

* **tracing overhead** — the ``bench_net`` UDP load generator runs
  untraced and then fully traced (a per-node :class:`repro.obs.Tracer`
  on every engine path + the analyzer pass); asserted: traced
  throughput stays within 10% of untraced. The trace bus must be cheap
  enough to leave on.

* **scrape cluster** — three real ``serve.py --listen --peers
  --metrics`` OS processes on loopback UDP; each serves its registry on
  an HTTP sidecar advertised through the ``--status-file`` heartbeat.
  The bench scrapes every member from the *outside* and asserts the
  replication-lag histogram and the byte-rate gauges are present and
  finite — the CI ``obs-smoke`` contract.

* **trace analysis** — the traced load generator's merged trace rolled
  up by :mod:`repro.obs.analyze`: reports the redundancy ratio (shipped
  bytes vs bytes that changed receiver state) and convergence rounds
  per write; asserts a converged cluster's trace carries zero
  ``ship_without_join`` anomalies.
"""

from __future__ import annotations

import asyncio
import json
import math
import os
import subprocess
import sys
import time
from typing import List, Tuple

from .bench_net import REPO_SRC, _free_ports, _udp_loadgen


# ---------------------------------------------------------------------------
# tracing overhead: traced loadgen within 10% of untraced
# ---------------------------------------------------------------------------

def _trace_overhead() -> Tuple[float, dict]:
    t0 = time.perf_counter()
    thr_plain, *_rest = asyncio.run(_udp_loadgen(traced=False))
    thr_traced, _p50, _p99, _wall, _stats, obs = asyncio.run(
        _udp_loadgen(traced=True))
    wall = time.perf_counter() - t0
    ratio = thr_traced / thr_plain
    assert ratio >= 0.90, (
        f"tracing cost more than 10% of throughput: {thr_traced:.0f} vs "
        f"{thr_plain:.0f} w/s ({ratio:.1%})")
    return wall, {"thr_plain": thr_plain, "thr_traced": thr_traced,
                  "ratio": ratio, "obs": obs}


# ---------------------------------------------------------------------------
# 3-process serve.py --metrics cluster, scraped from the outside
# ---------------------------------------------------------------------------

def _scrape_cluster(sessions: int = 12, timeout: float = 150.0
                    ) -> Tuple[float, dict]:
    from repro.obs import parse_prometheus, scrape

    ports = _free_ports(3)
    members = [f"gw{i}@127.0.0.1:{ports[i]}" for i in range(3)]
    # the children gossip session metadata only; on CPU they never
    # contend with this process for its accelerator
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "PYTHONPATH": REPO_SRC + (os.pathsep + os.environ["PYTHONPATH"]
                                     if os.environ.get("PYTHONPATH")
                                     else "")}
    import tempfile
    procs = []
    with tempfile.TemporaryDirectory(prefix="bench_obs_") as tmp:
        status = [os.path.join(tmp, f"status{i}.json") for i in range(3)]
        for i in range(3):
            peers = ",".join(m for j, m in enumerate(members) if j != i)
            procs.append(subprocess.Popen(
                [sys.executable, "-m", "repro.launch.serve",
                 "--listen", members[i], "--peers", peers,
                 "--sessions", str(sessions),
                 "--ship-policy", "bp+rr+digest-sync:4",
                 "--transport", "udp", "--tick", "0.1",
                 "--run-for", str(timeout),
                 "--status-file", status[i], "--metrics",
                 "--seed", str(i)],
                env=env, stdout=subprocess.PIPE,
                stderr=subprocess.PIPE, text=True))
        t0 = time.monotonic()
        agreed = None
        scraped = {}
        try:
            while time.monotonic() - t0 < timeout:
                time.sleep(0.5)
                for p in procs:
                    if p.poll() not in (None, 0):
                        _out, err = p.communicate()
                        raise AssertionError(
                            f"cluster member died: {err[-800:]}")
                try:
                    st = [json.load(open(f)) for f in status]
                except (FileNotFoundError, json.JSONDecodeError):
                    continue
                fps = {s["fingerprint"] for s in st}
                if (len(fps) == 1
                        and all(s["all_done"] and s["keys"] == sessions
                                for s in st)):
                    agreed = st
                    break
            assert agreed is not None, (
                f"3-process cluster did not agree within {timeout}s")
            # scrape each member's advertised sidecar while it still runs
            for s in agreed:
                addr = s["metrics_addr"]
                assert addr, f"{s['id']}: no metrics sidecar advertised"
                parsed = parse_prometheus(scrape(addr))
                nid = s["id"]
                for fam in ("repro_ack_lag_seconds_count",
                            "repro_net_bytes_sent_per_second",
                            "repro_replica_delta_buffer_depth",
                            "repro_net_frames_sent_total"):
                    assert fam in parsed, (nid, fam, sorted(parsed)[:30])
                    vals = list(parsed[fam].values())
                    assert all(math.isfinite(v) for v in vals), (nid, fam)
                rate = list(
                    parsed["repro_net_bytes_sent_per_second"].values())
                lag_n = sum(
                    parsed["repro_ack_lag_seconds_count"].values())
                scraped[nid] = {"byte_rate": rate[0], "acked_writes": lag_n}
                # the heartbeat itself carries the same snapshot
                assert "repro_replica_delta_buffer_depth" in s["metrics"]
        finally:
            for p in procs:
                p.terminate()
            for p in procs:
                try:
                    p.communicate(timeout=30)
                except subprocess.TimeoutExpired:  # pragma: no cover
                    p.kill()
        wall = time.monotonic() - t0
        lags = sum(v["acked_writes"] for v in scraped.values())
        return wall, {"scraped": sorted(scraped), "acked_writes": lags}


# ---------------------------------------------------------------------------


def run() -> List[Tuple[str, float, str]]:
    rows: List[Tuple[str, float, str]] = []

    wall, d = _trace_overhead()
    rows.append(("obs_trace_overhead", wall * 1e6 / 480,
                 f"traced={d['thr_traced']:.0f}w/s "
                 f"untraced={d['thr_plain']:.0f}w/s "
                 f"ratio={d['ratio']:.2f} (assert >=0.90)"))
    obs = d["obs"]
    rows.append(("obs_analyze_loadgen", float("nan"),
                 f"redundancy={obs['redundancy_ratio']:.2f} "
                 f"mean_rounds={obs['mean_rounds']:.1f} "
                 f"mean_lag={obs['mean_lag_s']*1e3:.0f}ms "
                 f"(real socket run, zero ship-without-join anomalies)"))

    wall, d = _scrape_cluster()
    rows.append(("obs_scrape_cluster", wall * 1e6,
                 f"3 serve.py --metrics procs scraped via sidecar HTTP: "
                 f"lag+byte-rate gauges present&finite on "
                 f"{d['scraped']}, {d['acked_writes']} acked writes "
                 f"observed"))
    return rows


if __name__ == "__main__":
    for name, us, derived in run():
        print(f"{name},{us:.1f},{derived}")
