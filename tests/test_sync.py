"""Cross-pod δ-CRDT sync runtime: delta-sync training convergence over
lossy links, top-k + error-feedback compression, elastic membership with
straggler eviction, duplicate-safe metrics."""

import random

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import NetConfig, Simulator, converged, run_to_convergence
from repro.core.tensor_lattice import DotSumStore
from repro.sync import (ClusterState, DeltaSyncPod, Membership, Metrics,
                        MetricsState, TopKCompressor)
from repro.sync.compression import dense_nbytes, sparse_nbytes


# ---------------------------------------------------------------------------
# Delta-sync (local SGD) training
# ---------------------------------------------------------------------------

def _init_params():
    return {"w": jnp.zeros((4,), jnp.float32), "b": jnp.zeros((), jnp.float32)}


def _mk_pods(n_pods, loss, seed, compressor_rate=None, ghost=True):
    sim = Simulator(NetConfig(loss=loss, dup=0.1, seed=seed))
    ids = [f"pod{k}" for k in range(n_pods)]

    def local_update(params, round_idx, pod_id):
        # deterministic "training": each pod pushes params toward
        # pod-specific target by 0.5 per round
        k = int(pod_id[3:])
        target = {"w": jnp.full((4,), float(k + 1)),
                  "b": jnp.asarray(float(k))}
        return jax.tree_util.tree_map(
            lambda p, t: p + 0.5 * (t - p), params, target)

    pods = []
    for i in ids:
        comp = TopKCompressor(compressor_rate) if compressor_rate else None
        pods.append(sim.add_node(DeltaSyncPod(
            i, [j for j in ids if j != i], _init_params(), local_update,
            num_pods=n_pods, compressor=comp,
            rng=random.Random(seed + hash(i) % 100), ghost_check=ghost)))
    return sim, pods


def test_delta_sync_pods_converge_over_lossy_network():
    sim, pods = _mk_pods(3, loss=0.3, seed=42)
    for rnd in range(4):
        for p in pods:
            p.do_round()
        sim.run_for(3.0)
    run_to_convergence(sim, pods, interval=1.0, max_time=20_000)
    assert converged(pods)
    # all pods materialize identical outer params
    ps = [p.params() for p in pods]
    for p in ps[1:]:
        assert np.allclose(np.asarray(ps[0]["w"]), np.asarray(p["w"]))
    # every (pod, round) dot was counted exactly once
    assert len(pods[0].X.dots) == 3 * 4
    for n in pods:
        assert not n.ghost_failures


def test_converged_pods_compare_payloads_on_device(monkeypatch):
    """Once two pods hold the same dots, the convergence check compares
    every payload on the device and reads back one bool, nothing else."""
    from repro.obs import global_registry

    def eq_bytes():
        got = global_registry().snapshot().get(
            "repro_dotstore_eq_bytes_total") or {}
        return {w: got.get(w, 0.0) for w in ("device", "host")}

    sim, pods = _mk_pods(2, loss=0.2, seed=5)
    for _ in range(2):
        for p in pods:
            p.do_round()
        sim.run_for(3.0)
    run_to_convergence(sim, pods, interval=1.0, max_time=20_000)
    assert len(pods[0].X.dots) == 2 * 2
    reads = []
    device_get = jax.device_get

    def spy(x):
        reads.append(x)
        return device_get(x)

    monkeypatch.setattr(jax, "device_get", spy)
    before = eq_bytes()
    assert converged(pods)
    after = eq_bytes()
    assert len(reads) == 1
    assert isinstance(reads[0], jax.Array)
    assert reads[0].shape == () and reads[0].dtype == jnp.bool_
    assert after["host"] == before["host"]
    compared = sum(leaf.nbytes for p in pods for _, upd in p.X.dots
                   for leaf in jax.tree_util.tree_leaves(upd))
    assert after["device"] - before["device"] == compared


def test_delta_sync_with_topk_compression_converges():
    sim, pods = _mk_pods(3, loss=0.2, seed=7, compressor_rate=0.5,
                         ghost=False)
    for rnd in range(3):
        for p in pods:
            p.do_round()
        sim.run_for(3.0)
    run_to_convergence(sim, pods, interval=1.0, max_time=20_000)
    ps = [p.params() for p in pods]
    for p in ps[1:]:
        assert np.allclose(np.asarray(ps[0]["w"]), np.asarray(p["w"]))


# ---------------------------------------------------------------------------
# Compression
# ---------------------------------------------------------------------------

def test_topk_keeps_largest_and_feeds_back_error():
    comp = TopKCompressor(rate=0.25)  # keep 4 of 16
    x = {"g": jnp.asarray(np.arange(16, dtype=np.float32))}
    s = comp.compress(x)
    dense = TopKCompressor.decompress(s)["g"]
    # kept exactly the 4 largest magnitudes
    assert set(np.nonzero(np.asarray(dense))[0]) == {12, 13, 14, 15}
    # residual carries the rest; next round with zero update ships them
    s2 = comp.compress({"g": jnp.zeros(16)})
    dense2 = TopKCompressor.decompress(s2)["g"]
    assert set(np.nonzero(np.asarray(dense2))[0]) == {8, 9, 10, 11}
    # nothing is ever lost: over rounds the sum converges to the original
    total = np.asarray(dense + dense2)
    for _ in range(3):
        total = total + np.asarray(TopKCompressor.decompress(
            comp.compress({"g": jnp.zeros(16)}))["g"])
    assert np.allclose(total, np.arange(16), atol=1e-5)


def test_sparse_payload_smaller_than_dense():
    comp = TopKCompressor(rate=0.01)
    x = {"g": jnp.asarray(np.random.default_rng(0)
                          .normal(size=(4096,)).astype(np.float32))}
    s = comp.compress(x)
    assert sparse_nbytes(s) < dense_nbytes(x) / 10


# ---------------------------------------------------------------------------
# Membership / straggler mitigation
# ---------------------------------------------------------------------------

def test_membership_join_heartbeat_straggler_evict():
    m0 = Membership("w0", timeout=10.0, evict_after=30.0)
    S = ClusterState.bottom()
    S = S.join(m0.announce(S, now=0.0))
    S = S.join(S.join_delta("w1", "w1", 0.0))
    S = S.join(S.join_delta("w2", "w2", 0.0))
    assert S.workers() == {"w0", "w1", "w2"}
    # w2 goes silent; w0/w1 keep beating
    for t in (5.0, 10.0, 15.0, 20.0, 25.0, 31.0):
        S = S.join(S.beat_delta("w0", t)).join(S.beat_delta("w1", t))
    assert S.stragglers(now=31.0, timeout=10.0) == {"w2"}
    assert S.alive(now=31.0, timeout=10.0) == {"w0", "w1"}
    # eviction removes the straggler
    S = S.join(m0.evictions(S, now=31.0))
    assert S.workers() == {"w0", "w1"}


def test_membership_rejoin_wins_over_concurrent_eviction():
    """Add-wins semantics: a pod that rejoins during a partition survives a
    concurrent eviction — elasticity without a coordinator."""
    base = ClusterState.bottom()
    base = base.join(base.join_delta("w0", "w0", 0.0))
    base = base.join(base.join_delta("w1", "w1", 0.0))
    # partition: w0 evicts w1; w1 concurrently re-announces itself
    evict = base.leave_delta("w0", "w1")
    rejoin = base.join_delta("w1", "w1", 50.0)
    healed = base.join(evict).join(rejoin)
    assert "w1" in healed.workers()
    healed2 = base.join(rejoin).join(evict)
    assert healed2 == healed  # order-independent


def test_quorum_barrier_ignores_stragglers():
    m = Membership("w0", timeout=5.0)
    S = ClusterState.bottom()
    for w in ("w0", "w1", "w2", "w3"):
        S = S.join(S.join_delta(w, w, 0.0))
    for t in (2.0, 4.0, 6.0):
        for w in ("w0", "w1", "w2"):  # w3 is slow
            S = S.join(S.beat_delta(w, t))
    q = m.quorum(S, now=6.0, fraction=0.5)
    assert q == {"w0", "w1", "w2"}  # progress without w3


# ---------------------------------------------------------------------------
# Metrics
# ---------------------------------------------------------------------------

def test_metrics_duplicate_safe_and_exact():
    a, b = Metrics("r0"), Metrics("r1")
    d1 = a.observe("loss", 2.0)
    d2 = a.observe("loss", 4.0)
    d3 = b.observe("loss", 6.0)
    # deliver with duplication and reordering
    merged = MetricsState.bottom().join(d3).join(d2).join(d2).join(d1).join(d3)
    assert merged.count("loss") == 3
    assert merged.total("loss") == 12.0
    assert merged.mean("loss") == 4.0
    assert merged.minimum("loss") == 2.0
    assert merged.maximum("loss") == 6.0


def test_metrics_stale_report_subsumed():
    a = Metrics("r0")
    old = a.observe("tokens", 100.0, weight=1)
    new = a.observe("tokens", 100.0, weight=1)   # n=2 now
    merged = MetricsState.bottom().join(new).join(old)  # stale arrives late
    assert merged.count("tokens") == 2
    assert merged.total("tokens") == 200.0
