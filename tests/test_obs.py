"""Observability subsystem tests: trace bus, metrics registry, derived
probes, scrape surface, and the trace analyzer.

The load-bearing properties:

* the tracer is a bounded, sampled, optionally file-backed ring whose
  JSONL sink round-trips through ``load_trace``; unknown event kinds
  fail loudly at the emit site;
* a traced simulator run produces a clean trace: writes, ships, joins
  and acks that the analyzer can roll up with zero anomalies, a
  redundancy ratio ≥ 1, and per-key convergence lag;
* the registry's families render valid Prometheus text and a JSON
  snapshot; absorbers mirror live stats objects without the call sites
  changing; collectors run at scrape time;
* ``ReplicaProbes`` / ``AckLagProbe`` read engine health straight off a
  live replica (buffer depth, GC horizon age, write→acked latency);
* ``KernelCounters`` is snapshot-and-diff only (no global reset);
* program spans land on a ``jax.profiler`` trace's host plane, nested
  as the calls nest (a resident join around its plan, dispatch and
  rebuild; a receive around its decode and join); ``trace_gc`` marks a
  collection and uninstalls cleanly; the store kernels and the train
  step carry ``jax.named_scope`` names;
* the scrape sidecar serves both views over real sockets;
* the synthetic-trace anomaly detectors fire on exactly the corrupted
  streams they claim to catch;
* ``sync.metrics`` is a live re-export shim over ``obs.registry``.
"""

import asyncio
import contextlib
import gc
import glob
import json
import os
import random
import re

import numpy as np
import pytest

from repro.core import (AWORSet, MVRegister, NetConfig, Replica, Simulator,
                        StoreReplica, converged, make_policy,
                        run_to_convergence)
from repro.obs import (AckLagProbe, EVENT_KINDS, MetricsServer, Registry,
                       ReplicaProbes, Tracer, anomalies, convergence,
                       load_trace, marker_lag_histogram, merge_events,
                       parse_prometheus, redundancy, report, scrape,
                       scrape_json, semantic_trace, span, trace_gc)


# ---------------------------------------------------------------------------
# Tracer mechanics
# ---------------------------------------------------------------------------

def test_tracer_ring_sink_and_clock(tmp_path):
    t = [0.0]
    path = str(tmp_path / "trace.jsonl")
    with Tracer(node="a", clock=lambda: t[0], capacity=4,
                sink=path) as tr:
        for i in range(6):
            t[0] = float(i)
            tr.emit("write", keys=[f"k{i}"], tag=i)
    evs = tr.events()
    assert len(evs) == 4                      # ring kept the newest 4
    assert [e["t"] for e in evs] == [2.0, 3.0, 4.0, 5.0]
    assert [e["seq"] for e in evs] == [2, 3, 4, 5]
    assert all(e["node"] == "a" for e in evs)
    disk = load_trace(path)                   # the sink kept all 6
    assert len(disk) == 6 and disk[0]["keys"] == ["k0"]


def test_tracer_rejects_unknown_kind():
    tr = Tracer(node="a")
    with pytest.raises(ValueError, match="unknown trace event kind"):
        tr.emit("delta_shiip", dst="b")
    assert "delta_ship" in EVENT_KINDS


def test_tracer_sampling_is_seeded():
    def run():
        tr = Tracer(node="a", sample=0.5, seed=7)
        for i in range(200):
            tr.emit("write", keys=["k"], tag=i)
        return [e["tag"] for e in tr.events()], tr.dropped
    kept1, dropped1 = run()
    kept2, dropped2 = run()
    assert kept1 == kept2 and dropped1 == dropped2    # reproducible
    assert 0 < len(kept1) < 200 and dropped1 == 200 - len(kept1)


def test_merge_events_orders_by_time_then_seq():
    a, b = Tracer(node="a", clock=lambda: 1.0), Tracer(node="b",
                                                       clock=lambda: 0.5)
    a.emit("write", keys=["x"], tag=0)
    a.emit("ack", src="b", tag=1)
    b.emit("write", keys=["y"], tag=0)
    merged = merge_events(a, b)
    assert [e["node"] for e in merged] == ["b", "a", "a"]
    assert [e["seq"] for e in merged if e["node"] == "a"] == [0, 1]


# ---------------------------------------------------------------------------
# Traced engine: simulator runs feed the analyzer
# ---------------------------------------------------------------------------

def _traced_sim(policy="bp+rr", n=3, writes=6, loss=0.2):
    ids = [f"n{k}" for k in range(n)]
    sim = Simulator(NetConfig(loss=loss, seed=5))
    tracers = {i: Tracer(node=i, clock=lambda: sim.time) for i in ids}
    nodes = [sim.add_node(StoreReplica(
        i, [j for j in ids if j != i], causal=True,
        policy=make_policy(policy), rng=random.Random(11),
        tracer=tracers[i])) for i in ids]
    for w in range(writes):
        nodes[w % n].update(f"k{w}", MVRegister, "write_delta",
                            ids[w % n], f"v{w}")
        sim.run_for(0.5)
    run_to_convergence(sim, nodes, interval=1.0)
    assert converged(nodes)
    return ids, nodes, list(tracers.values())


def test_traced_sim_run_is_clean_and_converged():
    ids, nodes, tracers = _traced_sim()
    rep = report(tracers, expect_converged=ids)
    assert rep["anomaly_list"] == []
    assert rep["unconverged_keys"] == {}
    assert rep["keys"] == 6
    assert rep["redundancy"]["ratio"] >= 1.0
    assert rep["redundancy"]["shipped_bytes"] > 0
    assert rep["mean_rounds"] >= 0.0 and rep["max_lag_s"] > 0.0
    counts = {}
    for tr in tracers:
        for k, v in tr.counts().items():
            counts[k] = counts.get(k, 0) + v
    assert counts["write"] == 6
    assert counts["delta_ship"] > 0 and counts["delta_join"] > 0
    assert counts["ack"] > 0                  # bp needs the ack stream


def test_traced_sim_gc_horizon_events():
    _, nodes, tracers = _traced_sim(writes=8)
    for n in nodes:
        n.gc_deltas()
    gc = [e for tr in tracers for e in tr.events()
          if e["kind"] == "gc_horizon_advance"]
    assert gc, "converged buffers never reported a GC advance"
    assert all(e["dropped"] > 0 and e["horizon"] > 0 for e in gc)
    # the advance events account exactly for what left the buffers
    by_node = {e["node"]: e for tr in tracers for e in tr.events()
               if e["kind"] == "gc_horizon_advance"}
    for n in nodes:
        if n.id in by_node:
            assert len(n.entries) <= by_node[n.id]["depth"]


def test_traced_digest_sync_emits_pull_round_events():
    _, _, tracers = _traced_sim(policy="bp+rr+digest-sync:2", writes=6)
    counts = {}
    for tr in tracers:
        for k, v in tr.counts().items():
            counts[k] = counts.get(k, 0) + v
    assert counts.get("digest_req", 0) > 0
    rep = report(tracers)
    assert rep["anomaly_list"] == []


def test_traced_reaper_lifecycle_events():
    from repro.lifecycle import ReaperProtocol
    from repro.sync import KeyOwnership

    ids = ["n0", "n1", "n2"]
    ownership = KeyOwnership(ids, replication=3)
    sim = Simulator(NetConfig(seed=9))
    tracers = {i: Tracer(node=i, clock=lambda: sim.time) for i in ids}
    nodes = [sim.add_node(StoreReplica(
        i, [j for j in ids if j != i], causal=True,
        policy=make_policy("bp+rr"), rng=random.Random(13),
        ownership=ownership, ttl=2.0, tracer=tracers[i])) for i in ids]
    for n in nodes:
        ReaperProtocol(n, ownership, grace=0.5, retry=1.0)
        sim.every(1.0, n.on_periodic)
    nodes[0].update("sess", MVRegister, "write_delta", "n0", "done")
    sim.run_for(60.0)
    assert all("sess" in n.X.tombstoned_keys() for n in nodes)
    evs = merge_events(*tracers.values())
    kinds = {e["kind"] for e in evs}
    assert {"reap_propose", "reap_ack", "reap_commit"} <= kinds
    commit = next(e for e in evs if e["kind"] == "reap_commit")
    assert commit["key"] == "sess" and commit["acks"] == 2


# ---------------------------------------------------------------------------
# Registry: families, rendering, collectors, absorbers
# ---------------------------------------------------------------------------

def test_registry_families_render_and_snapshot():
    reg = Registry()
    c = reg.counter("frames_total", "frames", ("node",))
    c.labels("a").inc(3)
    c.labels(node="b").inc()
    g = reg.gauge("depth", "buffered entries")
    g.set(4.5)
    h = reg.histogram("lag_seconds", "lag", buckets=(0.1, 1.0, 10.0))
    for v in (0.05, 0.5, 0.5, 5.0):
        h.observe(v)
    text = reg.render_prometheus()
    parsed = parse_prometheus(text)
    assert parsed["frames_total"] == {'node="a"': 3.0, 'node="b"': 1.0}
    assert parsed["depth"][""] == 4.5
    assert parsed["lag_seconds_bucket"]['le="1"'] == 3.0   # cumulative
    assert parsed["lag_seconds_bucket"]['le="+Inf"'] == 4.0
    assert parsed["lag_seconds_count"][""] == 4.0
    assert "# TYPE lag_seconds histogram" in text
    snap = reg.snapshot()
    assert snap["frames_total"] == {"a": 3.0, "b": 1.0}
    assert snap["depth"] == 4.5
    assert snap["lag_seconds"]["count"] == 4
    assert h.approx_quantile(0.5) == 1.0
    # the JSON view survives non-finite floats
    reg.gauge("weird").set(float("inf"))
    assert json.loads(reg.render_json())["weird"] == "inf"


def test_registry_is_idempotent_and_rejects_redeclaration():
    reg = Registry()
    a = reg.counter("x_total", "x", ("node",))
    assert reg.counter("x_total", "x", ("node",)) is a
    with pytest.raises(ValueError, match="already registered"):
        reg.gauge("x_total")
    with pytest.raises(ValueError, match="already registered"):
        reg.counter("x_total", "x", ("node", "peer"))
    with pytest.raises(ValueError, match="invalid metric name"):
        reg.counter("bad name")
    with pytest.raises(ValueError, match="reserved"):
        reg.histogram("h", "h", ("le",))
    with pytest.raises(ValueError, match="counters only go up"):
        a.labels("a").inc(-1)


def test_registry_gauge_set_function_and_collectors():
    reg = Registry()
    depth = [7]
    reg.gauge("live_depth").set_function(lambda: depth[0])
    seen = []
    reg.add_collector(lambda: seen.append(True))
    snap = reg.snapshot()
    assert snap["live_depth"] == 7.0 and seen == [True]
    depth[0] = 9
    assert reg.snapshot()["live_depth"] == 9.0


def test_absorb_link_stats_publishes_totals_and_finite_rates():
    from repro.net.stats import LinkStats

    stats = LinkStats()
    stats.record("delta", 100)
    stats.record("digest", 40)
    stats.record_recv("delta", 80)
    stats.queue_drops += 2
    clock = [100.0]
    reg = Registry()
    reg.absorb_link_stats(stats, node="gw0", clock=lambda: clock[0])
    snap = reg.snapshot()
    assert snap["repro_net_bytes_sent_total"]["gw0"] == 140.0
    assert snap["repro_net_bytes_by_kind_total"]["gw0,delta"] == 100.0
    assert snap["repro_net_bytes_recv_total"]["gw0"] == 80.0
    assert snap["repro_net_queue_drops_total"]["gw0"] == 2.0
    # rate gauges exist and are finite from the FIRST scrape on
    assert snap["repro_net_bytes_sent_per_second"]["gw0"] == 0.0
    stats.record("delta", 50)
    clock[0] += 10.0
    snap = reg.snapshot()
    assert snap["repro_net_bytes_sent_per_second"]["gw0"] == 5.0
    # the live stats object stayed the accumulator: no call-site churn
    assert stats.bytes_sent == 190


def test_absorb_crdt_metrics_surfaces_replicated_aggregates():
    from repro.sync import Metrics

    m = Metrics("r1")
    m.observe("lat", 2.0)
    m.observe("lat", 4.0)
    reg = Registry()
    reg.absorb_crdt_metrics(m, node="r1")
    snap = reg.snapshot()
    assert snap["repro_crdt_metric_count"]["r1,lat"] == 2.0
    assert snap["repro_crdt_metric_sum"]["r1,lat"] == 6.0


def test_sync_metrics_is_a_live_shim():
    import repro.sync.metrics as legacy
    from repro.obs import registry as home

    assert legacy.Metrics is home.Metrics
    assert legacy.MetricsState is home.MetricsState
    assert legacy.MetricRecord is home.MetricRecord


# ---------------------------------------------------------------------------
# Engine probes
# ---------------------------------------------------------------------------

def test_replica_probes_read_live_engine_state():
    ids, nodes, _ = _traced_sim(writes=4)
    reg = Registry()
    for n in nodes:
        ReplicaProbes(reg, n)
    snap = reg.snapshot()
    assert set(snap["repro_replica_delta_buffer_depth"]) == set(ids)
    # the gauges mirror the live engine maps exactly
    by_id = {n.id: n for n in nodes}
    for i in ids:
        r = by_id[i]
        assert snap["repro_replica_delta_buffer_depth"][i] == len(r.entries)
        assert snap["repro_replica_counter"][i] == r.c >= 1
        assert snap["repro_replica_rounds_total"][i] == r.rounds > 0
        age = snap["repro_replica_gc_horizon_age"][i]
        assert age == r.c - snap["repro_replica_gc_horizon"][i] >= 0
    assert all(v >= 0.0
               for v in snap["repro_replica_unacked_entries"].values())
    # a fresh write is immediately visible at the next scrape
    nodes[0].update("late", MVRegister, "write_delta", ids[0], 1)
    assert (reg.snapshot()["repro_replica_delta_buffer_depth"][ids[0]]
            == len(nodes[0].entries))


def test_ack_lag_probe_resolves_after_acks():
    ids = ["a", "b", "c"]
    sim = Simulator(NetConfig(seed=3))
    nodes = [sim.add_node(Replica(i, AWORSet.bottom(),
                                  [j for j in ids if j != i], causal=True,
                                  policy=make_policy("bp+rr"),
                                  rng=random.Random(1)))
             for i in ids]
    reg = Registry()
    probe = AckLagProbe(reg, nodes[0], clock=lambda: sim.time)
    for k in range(3):
        nodes[0].operation(lambda X, k=k: X.add_delta("a", f"x{k}"))
        probe.note_write()
    assert probe.poll() == 0                  # nothing acked yet
    run_to_convergence(sim, nodes, interval=1.0)
    assert probe.poll() == 3
    snap = reg.snapshot()
    assert snap["repro_ack_lag_seconds"]["a"]["count"] == 3
    assert snap["repro_ack_pending_writes"]["a"] == 0.0


def test_marker_lag_histogram_shared_family():
    reg = Registry()
    child = marker_lag_histogram(reg, node="gw0")
    child.observe(0.2)
    marker_lag_histogram(reg, node="gw0").observe(0.3)   # same child
    snap = reg.snapshot()
    assert snap["repro_marker_lag_seconds"]["gw0"]["count"] == 2


# ---------------------------------------------------------------------------
# Kernel launch observability
# ---------------------------------------------------------------------------

def test_kernel_counters_snapshot_and_diff_only():
    from repro.kernels import ops

    assert not hasattr(ops.counters, "reset")
    snap = ops.counters.snapshot()
    ops.record_launch("probe_op")
    diff = ops.counters.since(snap)
    assert diff["launches"] == 1 and diff["h2d_bytes"] == 0


# ---------------------------------------------------------------------------
# Program spans on the profiler's clock
# ---------------------------------------------------------------------------

@contextlib.contextmanager
def _profiled(log_dir):
    """Profile the block on the CPU; afterwards the yielded list holds
    every ``repro.*`` host span of the trace as ``(name, start_ns,
    end_ns)``."""
    import jax
    from jax.profiler import ProfileData

    spans = []
    jax.profiler.start_trace(str(log_dir))
    try:
        yield spans
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(str(log_dir), "**", "*.xplane.pb"),
                        recursive=True)
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend((ev.name, ev.start_ns,
                              ev.start_ns + ev.duration_ns)
                             for ev in line.events
                             if ev.name.startswith("repro."))


def _named(spans, name):
    return [s for s in spans if s[0] == "repro." + name]


def _inside(child, parent) -> bool:
    return parent[1] <= child[1] and child[2] <= parent[2]


def _resident_pair(wire=False):
    """Two causal resident store replicas recovered from one bulk store
    of 4 keys x 4 chunks of 32, and their simulator."""
    from repro.core.tensor_lattice import ChunkedTensor, TensorState
    from repro.core.store import LatticeStore
    from repro.kernels import resident
    from repro.wire import WireCodec

    rng = np.random.default_rng(0)
    base = LatticeStore.of({f"k{k}": TensorState.of({"w": ChunkedTensor(
        rng.normal(size=(4, 32)).astype(np.float32),
        np.ones((4,), np.int32))}, lamport=1) for k in range(4)})
    sim = Simulator(NetConfig(seed=0))
    codec = WireCodec(to_device=True) if wire else None
    reps = [sim.add_node(StoreReplica(i, [j for j in ("a", "b") if j != i],
                                      causal=True, wire=codec,
                                      resident=True))
            for i in ("a", "b")]
    for r in reps:
        r.recover((base, 0))
    resident.ensure(base)
    return sim, reps


def _row_delta(version, chunks=4, chunk=32, row=1):
    from repro.core.tensor_lattice import TensorState, sparse_chunks
    return TensorState.of({"w": sparse_chunks(
        chunks, np.array([row]), np.full((1, chunk), 0.5, np.float32),
        np.array([version], np.int32))}, lamport=version)


def test_span_is_the_profilers_annotation():
    import jax
    assert isinstance(jax.profiler.TraceAnnotation("x"), type(span("x")))
    with span("anything"):       # a no-op with no profiler running
        pass


def test_resident_put_records_join_around_its_children(tmp_path):
    sim, (a, _) = _resident_pair()
    with _profiled(tmp_path) as spans:
        a.put("k2", _row_delta(7))
    (join,) = _named(spans, "resident.join")
    for child in ("resident.plan", "resident.dispatch", "resident.rebuild"):
        (c,) = _named(spans, child)
        assert _inside(c, join), child
    assert not _named(spans, "python.gc")     # trace_gc not installed


def test_host_store_join_records_no_resident_span(tmp_path):
    from repro.core.store import LatticeStore
    from repro.core.tensor_lattice import ChunkedTensor, TensorState
    x = LatticeStore.of({"k": TensorState.of({"w": ChunkedTensor(
        np.ones((2, 8), np.float32), np.ones((2,), np.int32))})})
    with _profiled(tmp_path) as spans:
        x.join(LatticeStore.key_delta("k", _row_delta(3, 2, 8, 0)))
    assert not _named(spans, "resident.join")


def test_gossip_tick_nests_codec_and_joins_in_engine_spans(tmp_path):
    sim, (a, b) = _resident_pair(wire=True)
    a.put("k0", _row_delta(5))
    with _profiled(tmp_path) as spans:
        for r in (a, b):
            r.on_periodic()
            r.gc_deltas()
        sim.run_for(2.0)
        assert converged([a, b])
        b.get("k0")
    periodic = _named(spans, "engine.periodic")
    receive = _named(spans, "engine.receive")
    assert len(periodic) == 2 and receive
    assert len(_named(spans, "engine.gc_deltas")) == 2
    encodes, decodes = _named(spans, "wire.encode"), _named(spans,
                                                            "wire.decode")
    # deltas leave in a periodic broadcast, acks in the receive of one
    assert any(_inside(e, p) for e in encodes for p in periodic)
    assert all(any(_inside(e, p) for p in periodic + receive)
               for e in encodes)
    assert len(decodes) == len(receive)
    assert all(any(_inside(d, r) for r in receive) for d in decodes)
    # b's join of a's delta happens inside b's receive of it
    joins = _named(spans, "resident.join")
    assert joins and all(any(_inside(j, r) for r in receive)
                         for j in joins)
    assert len(_named(spans, "antientropy.converged")) == 1
    assert len(_named(spans, "store.get")) == 1


def test_delta_sync_params_is_a_span(tmp_path):
    from repro.sync import DeltaSyncPod
    pod = DeltaSyncPod("pod0", ["pod1"], {"w": np.zeros(3, np.float32)},
                       lambda p, r, i: p, num_pods=2)
    with _profiled(tmp_path) as spans:
        pod.params()
    assert len(_named(spans, "sync.params")) == 1


def test_trace_gc_marks_a_collection_and_uninstalls(tmp_path):
    before = list(gc.callbacks)
    with _profiled(tmp_path) as spans:
        uninstall = trace_gc()
        try:
            gc.collect()
        finally:
            uninstall()
        gc.collect()                       # after: not marked
    assert gc.callbacks == before
    # one full collection, marked once
    assert len(_named(spans, "python.gc")) == 1


def test_kernel_launch_bridge_is_gone():
    from repro.kernels import ops
    assert "kernel_launch" not in EVENT_KINDS
    assert not hasattr(ops, "set_launch_hook")
    with pytest.raises(ValueError, match="unknown trace event kind"):
        Tracer().emit("kernel_launch", op="x")


@pytest.mark.parametrize("jitted,args,module", [
    ("_scatter_join_ref_jit", "scatter", "jit_scatter_join_ref"),
    ("_fused_join_digest_ref_jit", "fused", "jit_fused_join_digest_ref"),
    ("_chunk_digest_ref_jit", "digest", "jit_chunk_digest_ref"),
])
def test_store_kernels_carry_named_scopes(jitted, args, module):
    import jax.numpy as jnp
    from repro.kernels import ops
    rows, w = 8, 16
    vals, vers = jnp.zeros((rows, w)), jnp.zeros((rows,), jnp.int32)
    col = jnp.zeros((rows,))
    operands = {
        "scatter": (vals, vers, col, col, jnp.arange(2, dtype=jnp.int32),
                    jnp.ones((2, w)), jnp.ones((2,), jnp.int32)),
        "fused": (vals, vers, vals, vers),
        "digest": (vals,),
    }[args]
    text = getattr(ops, jitted).lower(*operands).as_text(debug_info=True)
    assert f"module @{module} " in text          # the module keeps its name
    assert f"store.{module[len('jit_'):]}" in text


def test_train_step_ops_are_named_by_part():
    import jax
    import jax.numpy as jnp
    from repro.configs import get_config
    from repro.models import init_model
    from repro.optim.adamw import init_opt_state
    from repro.runtime.steps import make_train_step

    cfg = get_config("qwen1.5-0.5b", reduced=True)
    params = jax.eval_shape(
        lambda: init_model(cfg, jax.random.PRNGKey(0))[0])
    opt = jax.eval_shape(init_opt_state, params)
    tok = jax.ShapeDtypeStruct((2, 16), jnp.int32)
    text = jax.jit(make_train_step(cfg)).lower(
        params, opt, {"tokens": tok, "labels": tok}).as_text(
            debug_info=True)
    # a scope reads ``jit(train_step)/jvp(layers)/...`` on the forward
    # pass and ``transpose(jvp(layers))`` on the backward
    for part in ("embed", "layers", "attention", "mlp", "head", "loss",
                 "adamw"):
        assert re.search(rf"[/(]{part}[)/]", text), part


# ---------------------------------------------------------------------------
# Scrape surface
# ---------------------------------------------------------------------------

def test_metrics_server_serves_both_views_over_sockets():
    reg = Registry()
    reg.counter("hits_total", "hits").inc(5)
    reg.gauge("depth", "d").set(2.0)

    async def scenario():
        server = MetricsServer(reg)
        addr = await server.start()
        try:
            text = await asyncio.to_thread(scrape, addr)
            js = await asyncio.to_thread(scrape_json, addr)
            with pytest.raises(RuntimeError, match="404"):
                await asyncio.to_thread(scrape, addr, "/nope")
            return text, js
        finally:
            await server.stop()

    text, js = asyncio.run(scenario())
    parsed = parse_prometheus(text)
    assert parsed["hits_total"][""] == 5.0
    assert js == {"hits_total": 5.0, "depth": 2.0}


# ---------------------------------------------------------------------------
# Analyzer on synthetic traces: each detector fires on its corruption
# ---------------------------------------------------------------------------

def _ev(kind, node, t, **f):
    return {"t": t, "seq": f.pop("seq", 0), "node": node, "kind": kind,
            **f}


def test_redundancy_counts_wasted_ships():
    trace = [
        _ev("delta_ship", "a", 0.0, dst="b", bytes=100, keys=["k"],
            full=False, tag=1),
        _ev("delta_join", "b", 0.1, src="a", via="delta", bytes=100,
            keys=["k"], joined=1),
        _ev("delta_ship", "a", 0.2, dst="b", bytes=100, keys=["k"],
            full=False, tag=1),
        _ev("delta_join", "b", 0.3, src="a", via="delta", bytes=100,
            keys=[], joined=0),
    ]
    red = redundancy(trace)
    assert red["ratio"] == 2.0
    assert red["redundant_joins"] == 1 and red["joins"] == 2


def test_convergence_measures_lag_and_rounds():
    trace = [
        _ev("write", "a", 1.0, keys=["k"], tag=0, round=3),
        _ev("delta_ship", "a", 1.5, dst="b", bytes=10, keys=["k"],
            full=False, tag=1, round=4),
        _ev("delta_join", "b", 2.0, src="a", via="delta", bytes=10,
            keys=["k"], joined=1, round=1),
        _ev("delta_ship", "a", 2.5, dst="c", bytes=10, keys=["k"],
            full=False, tag=1, round=5),
        _ev("delta_join", "c", 4.0, src="a", via="delta", bytes=10,
            keys=["k"], joined=1, round=1),
    ]
    conv = convergence(trace)
    assert conv["k"]["lag_s"] == 3.0          # last write → last join
    assert conv["k"]["rounds"] == 2           # two distinct ship rounds
    assert conv["k"]["nodes"] == ["a", "b", "c"]
    assert conv["k"]["writers"] == ["a"]


def test_anomaly_ack_without_and_above_ship():
    trace = [
        _ev("ack", "a", 0.5, src="b", tag=3, stale=False),
        _ev("delta_ship", "a", 1.0, dst="c", bytes=10, keys=["k"],
            full=False, tag=2),
        _ev("ack", "a", 1.5, src="c", tag=9, stale=False),
    ]
    kinds = [a["kind"] for a in anomalies(trace)]
    assert kinds.count("ack_without_ship") == 1
    assert kinds.count("ack_above_ship") == 1


def test_anomaly_ship_before_have_and_without_join():
    trace = [
        _ev("write", "a", 0.0, keys=["k"], tag=0),
        _ev("delta_ship", "a", 0.1, dst="b", bytes=10, keys=["k"],
            full=False, tag=1),
        _ev("delta_ship", "b", 0.2, dst="a", bytes=10, keys=["k"],
            full=False, tag=1),              # b never wrote/joined k
    ]
    kinds = [a["kind"] for a in anomalies(trace)]
    assert "ship_before_have" in kinds
    assert "ship_without_join" in kinds       # k never joined anywhere
    # a full-state ship is exempt (bootstrap legitimately ships unknowns)
    trace[2] = _ev("delta_ship", "b", 0.2, dst="a", bytes=10,
                   keys=["k"], full=True)
    assert "ship_before_have" not in [a["kind"] for a in anomalies(trace)]


def test_anomaly_checks_disabled_on_truncation():
    trace = [
        _ev("write", "a", 0.0, keys=["k"], tag=0),
        _ev("delta_ship", "b", 0.2, dst="a", bytes=10, keys=["k"],
            full=False, tag=1, keys_truncated=True),
    ]
    kinds = [a["kind"] for a in anomalies(trace)]
    assert kinds == ["keys_truncated"]        # no false positives


def test_semantic_trace_is_timing_free():
    fast = [
        _ev("write", "a", 0.0, keys=["k"], tag=0),
        _ev("delta_join", "b", 0.1, src="a", via="delta", bytes=5,
            keys=["k"], joined=1),
    ]
    slow = [                                   # same story, other timing
        _ev("write", "a", 7.0, keys=["k"], tag=0),
        _ev("delta_join", "b", 93.0, src="c", via="digest-resp",
            bytes=999, keys=["k"], joined=1),
        _ev("delta_join", "b", 94.0, src="a", via="delta", bytes=5,
            keys=[], joined=0),                # redundant: not semantic
    ]
    assert semantic_trace(fast) == semantic_trace(slow)
    assert semantic_trace(fast) == {
        "k": {"writes": {"a": 1}, "joined": ["a", "b"]}}


# ---------------------------------------------------------------------------
# The full loop on real sockets: traced cluster, probes, scrape, analyze
# ---------------------------------------------------------------------------

def test_traced_socket_cluster_scrape_and_analyze():
    from repro.net import start_cluster, stop_cluster, wait_converged

    tracers = {}

    def tf(node_id):
        tracers[node_id] = Tracer(node=node_id)
        return tracers[node_id]

    async def scenario():
        nodes = await start_cluster(3, transport="udp", tick=0.03,
                                    seed=61, tracer_factory=tf)
        try:
            addrs = []
            for n in nodes:
                n.export_metrics()
                addrs.append(await n.serve_metrics())
            for k, n in enumerate(nodes):
                n.update(f"s{k}", MVRegister, "write_delta", n.id, "done")
            await wait_converged(nodes, timeout=30.0)
            await asyncio.sleep(0.2)          # let trailing acks land
            texts = [await asyncio.to_thread(scrape, a) for a in addrs]
            return [n.id for n in nodes], texts
        finally:
            await stop_cluster(nodes)

    ids, texts = asyncio.run(scenario())
    for nid, text in zip(ids, texts):
        parsed = parse_prometheus(text)
        assert parsed["repro_net_frames_sent_total"][f'node="{nid}"'] > 0
        assert f'node="{nid}"' in parsed["repro_net_bytes_sent_per_second"]
        assert f'node="{nid}"' in parsed["repro_replica_delta_buffer_depth"]
        assert parsed["repro_ack_lag_seconds_count"][f'node="{nid}"'] >= 1
    rep = report(list(tracers.values()), expect_converged=ids)
    assert rep["anomaly_list"] == []
    assert rep["unconverged_keys"] == {}
    assert rep["redundancy"]["ratio"] >= 1.0
