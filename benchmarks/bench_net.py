"""Real-network benchmark: load-generated gossip over loopback sockets.

Three scenarios, each asserting the production claim it measures:

* **UDP load generator** — a 3-node in-process cluster on real loopback
  UDP sockets with 10% injected datagram loss; a load generator drives
  sustained multi-client write traffic and samples marker keys to
  measure *convergence latency* (write → visible on every node).
  Reports throughput and p50/p99 latency; asserts every marker
  converged under loss (δ-drops are repaired by acks + digest-sync,
  never fatal).

* **TCP kill/restart** — a 3-node TCP cluster; one member is killed
  mid-run (durable state snapshotted, sockets aborted), the survivors
  keep writing, and the member restarts on the same port. The dialers
  reconnect and digest-sync pulls exactly what it missed: asserted to
  cost a small fraction of re-shipping the survivors' full state.

* **3-process cluster** — the real thing: three ``serve.py --listen
  --peers`` OS processes on loopback UDP with injected loss, each
  writing its share of the session keys, observed purely from the
  outside via ``--status-file`` heartbeats until their semantic
  fingerprints agree. This is the row the CI ``net-smoke`` job runs.
  A second, zone-annotated variant (``gwN@host:port@zN``) runs the same
  cluster under hierarchical gossip and asserts the heartbeats report
  each member's zone and per-link-class byte counters.

Byte numbers are ``LinkStats`` — the same per-payload-kind counters the
simulator's ``NetStats`` reports, so these rows compare directly with
``bench_wire``'s sim rows.
"""

from __future__ import annotations

import asyncio
import json
import os
import socket
import subprocess
import sys
import tempfile
import time
from typing import List, Tuple

REPO_SRC = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "src")


def _percentile(xs: List[float], p: float) -> float:
    ys = sorted(xs)
    if not ys:
        return float("nan")
    i = min(len(ys) - 1, max(0, int(round(p * (len(ys) - 1)))))
    return ys[i]


# ---------------------------------------------------------------------------
# UDP load generator: throughput + convergence latency under loss
# ---------------------------------------------------------------------------

async def _udp_loadgen(n_writes: int = 240, keyspace: int = 48,
                       marker_every: int = 8, loss: float = 0.10,
                       traced: bool = True
                       ) -> Tuple[float, float, float, float, dict, dict]:
    from repro.core import MVRegister
    from repro.net import start_cluster, stop_cluster, wait_converged
    from repro.obs import (Tracer, global_registry, marker_lag_histogram,
                           report)

    tracers: dict = {}

    def tracer_factory(node_id):
        tracers[node_id] = Tracer(node=node_id)
        return tracers[node_id]

    nodes = await start_cluster(3, transport="udp", tick=0.05,
                                loss=loss, seed=11,
                                tracer_factory=(tracer_factory if traced
                                                else None))
    lat: List[float] = []
    pending: dict = {}

    def sweep() -> None:
        for mk, t0 in list(pending.items()):
            if all(n.replica.get(mk, MVRegister) is not None
                   for n in nodes):
                lat.append(time.monotonic() - t0)
                del pending[mk]

    t_start = time.monotonic()
    for i in range(n_writes):
        node = nodes[i % len(nodes)]          # multi-client ingress
        node.update(f"k{i % keyspace}", MVRegister, "write_delta",
                    node.id, i)
        if i % marker_every == 0:
            mk = f"m{i}"
            node.update(mk, MVRegister, "write_delta", node.id, i)
            pending[mk] = time.monotonic()
        sweep()
        await asyncio.sleep(0.002)            # sustained, not bursty
    write_wall = time.monotonic() - t_start
    # drain: every marker must land everywhere despite the lossy mesh
    deadline = time.monotonic() + 30.0
    while pending and time.monotonic() < deadline:
        sweep()
        await asyncio.sleep(0.02)
    assert not pending, (f"{len(pending)} markers never converged under "
                         f"{loss:.0%} UDP loss")
    await wait_converged(nodes, timeout=30.0)
    await asyncio.sleep(0.2)                  # let trailing acks land
    stats = nodes[0].stats.summary()
    losses = sum(getattr(n.transport, "injected_losses", 0) for n in nodes)
    stats["injected_losses"] = losses
    ids = [n.id for n in nodes]
    queue_drops = sum(n.stats.queue_drops for n in nodes)
    await stop_cluster(nodes)
    thr = n_writes / write_wall

    obs = {}
    if traced:
        # the marker lags ARE per-key replication lag: publish them on
        # the process-wide registry (run.py --json snapshots it per
        # suite), alongside the suite's shed-frame total
        reg = global_registry()
        child = marker_lag_histogram(reg, node="bench_net")
        for v in lat:
            child.observe(v)
        reg.counter("repro_net_queue_drops_total",
                    "frames shed by bounded send queues",
                    ("node",)).labels("bench_net").set_total(queue_drops)
        # the analyzer closes the loop: a converged cluster's trace must
        # be anomaly-free, and the redundancy ratio quantifies what the
        # shipping policy paid over the minimum
        rep = report(list(tracers.values()), expect_converged=ids)
        assert rep["anomalies"].get("ship_without_join", 0) == 0, \
            rep["anomaly_list"]
        assert rep["anomaly_list"] == [], rep["anomaly_list"]
        assert rep["unconverged_keys"] == {}, rep["unconverged_keys"]
        reg.gauge("repro_bench_redundancy_ratio",
                  "shipped bytes / state-changing joined bytes",
                  ("suite",)).labels("net").set(rep["redundancy"]["ratio"])
        obs = {"redundancy_ratio": rep["redundancy"]["ratio"],
               "mean_rounds": rep["mean_rounds"],
               "mean_lag_s": rep["mean_lag_s"]}
    return thr, _percentile(lat, 0.50), _percentile(lat, 0.99), \
        write_wall, stats, obs


# ---------------------------------------------------------------------------
# TCP kill/restart: reconnect catches up via digest-sync
# ---------------------------------------------------------------------------

async def _tcp_kill_restart(pre_keys: int = 160, post_keys: int = 8
                            ) -> Tuple[float, int, int, float]:
    from repro.core import MVRegister
    from repro.net import (GossipNode, default_replica_factory,
                           start_cluster, stop_cluster, wait_converged)
    from repro.wire import encode_frame, encode_value

    # pure pull: the restarted member trades one digest per round and
    # receives exactly the rows it lacks — the cleanest reconnect story
    # (the hybrid's push path would re-ship a barely-filtered causal
    # interval before the first pull round even fires)
    policy = "digest-sync"
    nodes = await start_cluster(3, transport="tcp", tick=0.05,
                                policy=policy, seed=23)
    for s in range(pre_keys):
        n = nodes[s % 3]
        for status in ("queued", "done"):
            n.update(f"sess{s}", MVRegister, "write_delta", n.id, status)
    await wait_converged(nodes, timeout=30.0)

    victim = nodes[2]
    durable = victim.replica.durable_snapshot()   # what a crash keeps
    addr = victim.addr
    await victim.stop(abort=True)                 # kill: sockets torn down

    survivors = nodes[:2]
    for s in range(pre_keys, pre_keys + post_keys):
        n = survivors[s % 2]
        for status in ("queued", "done"):
            n.update(f"sess{s}", MVRegister, "write_delta", n.id, status)
    await wait_converged(survivors, timeout=30.0)

    # restart on the same port with the durable snapshot — peers'
    # dialers reconnect, digest-sync pulls the gap
    reborn = GossipNode(victim.id, addr, transport="tcp", policy=policy,
                        peers={p.id: p.addr for p in survivors}, tick=0.05)
    replica = default_replica_factory(policy)(victim.id,
                                              sorted(p.id for p in
                                                     survivors))
    replica.recover(durable)
    reborn.adopt_replica(replica)
    t0 = time.monotonic()
    await reborn.start()
    allnodes = [*survivors, reborn]
    await wait_converged(allnodes, timeout=30.0)
    catchup_s = time.monotonic() - t0

    catchup_bytes = reborn.stats.recv_state_bytes()
    full_bytes = len(encode_frame("state",
                                  encode_value(survivors[0].X)))
    await stop_cluster(allnodes)
    return catchup_s, catchup_bytes, full_bytes, \
        catchup_bytes / max(full_bytes, 1)


# ---------------------------------------------------------------------------
# 3 OS processes via serve.py --listen/--peers (the CI net-smoke row)
# ---------------------------------------------------------------------------

def _free_ports(n: int) -> List[int]:
    socks = [socket.socket() for _ in range(n)]
    for s in socks:
        s.bind(("127.0.0.1", 0))
    ports = [s.getsockname()[1] for s in socks]
    for s in socks:
        s.close()
    return ports


def _process_cluster(sessions: int = 24, loss: float = 0.10,
                     timeout: float = 150.0,
                     zones: bool = False) -> Tuple[float, dict]:
    ports = _free_ports(3)
    members = [f"gw{i}@127.0.0.1:{ports[i]}" + (f"@z{i}" if zones else "")
               for i in range(3)]
    # the children gossip session metadata only; on CPU they never
    # contend with this process for its accelerator
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "PYTHONPATH": REPO_SRC + (os.pathsep + os.environ["PYTHONPATH"]
                                     if os.environ.get("PYTHONPATH")
                                     else "")}
    procs = []
    with tempfile.TemporaryDirectory(prefix="bench_net_") as tmp:
        status = [os.path.join(tmp, f"status{i}.json") for i in range(3)]
        for i in range(3):
            peers = ",".join(m for j, m in enumerate(members) if j != i)
            procs.append(subprocess.Popen(
                [sys.executable, "-m", "repro.launch.serve",
                 "--listen", members[i], "--peers", peers,
                 "--sessions", str(sessions),
                 "--ship-policy", "bp+rr+digest-sync:4",
                 "--transport", "udp", "--udp-loss", str(loss),
                 "--tick", "0.1", "--run-for", str(timeout),
                 "--status-file", status[i], "--seed", str(i)],
                env=env, stdout=subprocess.PIPE,
                stderr=subprocess.PIPE, text=True))
        t0 = time.monotonic()
        agreed = None
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
        finally:
            for p in procs:
                p.terminate()
            for p in procs:
                try:
                    p.communicate(timeout=30)
                except subprocess.TimeoutExpired:  # pragma: no cover
                    p.kill()
        assert agreed is not None, (
            f"3-process cluster did not agree within {timeout}s")
        wall = time.monotonic() - t0
        if zones:
            # heartbeats must carry the zone + per-link-class counters
            assert [s["zone"] for s in agreed] == ["z0", "z1", "z2"]
            for s in agreed:
                assert s["bytes_by_class"], s
            return wall, {"bytes_by_class": agreed[0]["bytes_by_class"],
                          "zones": [s["zone"] for s in agreed]}
        bytes_by_kind = agreed[0]["bytes_by_kind"]
        return wall, bytes_by_kind


# ---------------------------------------------------------------------------


def run() -> List[Tuple[str, float, str]]:
    rows: List[Tuple[str, float, str]] = []

    thr, p50, p99, wall, stats, obs = asyncio.run(_udp_loadgen())
    assert p99 < 10.0, f"p99 convergence latency {p99:.2f}s under loss"
    rows.append(("net_udp_loadgen", wall * 1e6 / 240,
                 f"thr={thr:.0f}w/s p50={p50*1e3:.0f}ms "
                 f"p99={p99*1e3:.0f}ms loss=0.10 "
                 f"lost_datagrams={stats['injected_losses']} "
                 f"queue_drops={stats['queue_drops']} "
                 f"redundancy={obs['redundancy_ratio']:.2f} "
                 f"rounds={obs['mean_rounds']:.1f} all markers "
                 f"converged, trace anomaly-free"))

    catchup_s, catchup_b, full_b, ratio = asyncio.run(_tcp_kill_restart())
    assert ratio <= 0.25, (
        f"restart catch-up cost {ratio:.1%} of full state — digest-sync "
        f"should make a reconnect cheap ({catchup_b}B vs {full_b}B)")
    rows.append(("net_tcp_kill_restart", catchup_s * 1e6,
                 f"catchup_bytes={catchup_b} full_state_frame={full_b} "
                 f"ratio={ratio:.1%} (assert <=25%) reconnected+converged "
                 f"in {catchup_s:.2f}s"))

    wall, by_kind = _process_cluster()
    payload = {k: v for k, v in sorted(by_kind.items())}
    rows.append(("net_3proc_serve_cluster", wall * 1e6,
                 f"3 serve.py procs (udp loss=0.10) fingerprint-agreed "
                 f"in {wall:.1f}s bytes_by_kind={payload}"))

    wall, zoned = _process_cluster(sessions=12, zones=True)
    by_class = dict(sorted(zoned["bytes_by_class"].items()))
    rows.append(("net_3proc_zoned_cluster", wall * 1e6,
                 f"3 serve.py procs in 3 zones (udp loss=0.10, "
                 f"hierarchical gossip) fingerprint-agreed in {wall:.1f}s "
                 f"zones={zoned['zones']} bytes_by_class={by_class}"))
    return rows


if __name__ == "__main__":
    for name, us, derived in run():
        print(f"{name},{us:.1f},{derived}")
