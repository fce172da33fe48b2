"""Driver for keyed-store deployments: replicas of the delta-CRDT keyed
store, device-resident on one chip, gossiping over the lossy simulated
network, under open-loop client traffic.

One service loop serves every operation in due order and runs the
gossip ticks between them: an update is one ``StoreReplica.put`` of one
record (a chunk row), a read is one ``StoreReplica.get`` with the
record's row brought to the host, and a tick is ``on_periodic`` and
``gc_deltas`` on every replica followed by the simulator delivering (or
losing) that tick's messages and their acks. The simulator's virtual
delays are not waited for in wall time.

Latencies count from each operation's due time: an update is visible
when all replicas hold a version at least as new for its record (read
after every tick), a read is done when the record's row is on the host.
After the window the run serves what is still due, waits until every
update is visible (at most the traffic's ``drain_limit_s``), gossips to
convergence,
and compares every replica's columns and every read with the numpy
reference in ``bench/reference/store_ref.py``.
"""

from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Dict, List

import numpy as np

from .. import work, workgen
from ..reference import store_ref

RANK_BITS = 10                       # version = (op counter << 10) | writer
BASE_VERSION = 1 << RANK_BITS
CONVERGE_TICKS = 300


def _key(k: int) -> str:
    return f"k{k:06d}"


def make_base(seed: int, n_rows: int, width: int) -> np.ndarray:
    """The store's initial values, made on the device in one jitted call
    from the seed and brought to the host (the store loads from host)."""
    import jax
    import jax.numpy as jnp
    from ..seeds import jax_key
    gen = jax.jit(lambda k: jax.random.normal(k, (n_rows, width),
                                              jnp.float32))
    return np.asarray(gen(jax_key(seed, 0)))


@dataclass
class _Log:
    """Every update and read the run made, for latencies and the
    reference."""
    upd_row: List[int] = field(default_factory=list)
    upd_ver: List[int] = field(default_factory=list)
    upd_val: List[np.ndarray] = field(default_factory=list)
    reads: List[tuple] = field(default_factory=list)


class Cluster:
    """The replicas, the simulator and the loop that drives them."""

    def __init__(self, config: dict, base_vals: np.ndarray, rec,
                 seed: int):
        from repro.core import NetConfig, Simulator, StoreReplica
        from repro.core.store import LatticeStore
        from repro.core.tensor_lattice import ChunkedTensor, TensorState
        from repro.kernels import resident
        from repro.wire.frames import WireCodec

        self.rec = rec
        self.per = int(config["records_per_key"])
        self.n_keys = int(config["keys"])
        self.n_rows = self.n_keys * self.per
        self.width = int(config["record_floats"])
        self.tick_s = float(config["gossip_interval_s"])
        self.base_vals = base_vals
        self.base_vers = np.full(self.n_rows, BASE_VERSION, np.int32)
        per = self.per
        base = LatticeStore.of({
            _key(k): TensorState.of({"w": ChunkedTensor(
                base_vals[k * per:(k + 1) * per],
                self.base_vers[k * per:(k + 1) * per])}, lamport=1)
            for k in range(self.n_keys)})
        # which messages are lost or duplicated is drawn from the run's
        # seed, so the comparison meets another loss pattern in every run
        net = config["net"]
        self.sim = Simulator(NetConfig(loss=float(net["loss"]),
                                       dup=float(net["dup"]), seed=int(seed)))
        ids = [f"r{i}" for i in range(int(config["replicas"]))]
        wire = WireCodec(to_device=bool(config["wire_to_device"]))
        self.reps = [self.sim.add_node(StoreReplica(
            i, [j for j in ids if j != i], causal=config["mode"] == "causal",
            wire=wire, resident=bool(config["resident"]))) for i in ids]
        # every replica recovers the same durable bulk-loaded store; the
        # store is immutable, so they share its columns until each one's
        # first join makes columns of its own
        for r in self.reps:
            r.recover((base, 0))
        resident.ensure(base)
        self._resident = resident
        self._ts = TensorState
        from repro.core.tensor_lattice import sparse_chunks
        self._sparse = sparse_chunks
        self.log = _Log()
        self.last_local: Dict[tuple, int] = {}
        self.n_ops = 0                        # version counter
        self.next_tick = 0.0
        self.pending: List[tuple] = []        # (row, version, due, measured)

    # -- calls into the program -------------------------------------------
    def columns(self, rep):
        cache = self._resident.resident_of(rep.store)
        if cache is None:
            raise RuntimeError(f"{rep.id}: the store left the device")
        return cache

    def put(self, client: int, row: int, values: np.ndarray) -> int:
        version = ((self.n_ops + 2) << RANK_BITS) | (client + 1)
        self.n_ops += 1
        k, c = divmod(int(row), self.per)
        delta = self._ts.of({"w": self._sparse(
            self.per, np.array([c]), values[None, :],
            np.array([version], np.int32))}, lamport=version >> RANK_BITS)
        rep = self.reps[client]
        with self.rec.span("put"):
            rep.put(_key(k), delta)
            self.columns(rep).vals.block_until_ready()
        self.log.upd_row.append(int(row))
        self.log.upd_ver.append(version)
        self.log.upd_val.append(values)
        key = (client, int(row))
        self.last_local[key] = max(self.last_local.get(key, 0), version)
        return version

    def get(self, client: int, row: int):
        k, c = divmod(int(row), self.per)
        rep = self.reps[client]
        with self.rec.span("get"):
            ct = rep.get(_key(k)).as_dict()["w"]
            vals = np.asarray(ct.values[c])
            ver = int(np.asarray(ct.versions)[c])
        return ver, vals

    def tick(self) -> None:
        with self.rec.span("tick"):
            for r in self.reps:
                r.on_periodic()
                r.gc_deltas()
            self.sim.run_for(2.0)

    def held(self, rows: np.ndarray) -> np.ndarray:
        """Lowest version any replica holds for each of ``rows``."""
        return np.min([self.columns(r).vers_host[rows] for r in self.reps],
                      axis=0)

    def converged(self) -> bool:
        v0, *rest = [self.columns(r).vers_host for r in self.reps]
        return all(np.array_equal(v0, v) for v in rest)

    # -- the service loop -----------------------------------------------------
    def serve(self, ops: workgen.OpenLoopOps, t0: float, until: float,
              measured: bool, start: int = 0, done=None) -> int:
        """Serve ``ops[start:]`` (due times relative to ``t0``) and the
        gossip ticks in due order until ``until`` seconds after ``t0`` or
        until ``done()``; returns the index of the next unserved op."""
        rec = self.rec
        i = start
        n = len(ops)
        while True:
            now = time.perf_counter() - t0
            if now >= until or (done is not None and done(i)):
                return i
            due = ops.due[i] if i < n else float("inf")
            if self.next_tick <= due:
                if self.next_tick > now:
                    time.sleep(min(self.next_tick, until) - now)
                    continue
                self.tick()
                self._mark_visible()
                now = time.perf_counter() - t0
                while self.next_tick <= now:
                    self.next_tick += self.tick_s
                continue
            if due > now:
                time.sleep(min(due, until) - now)
                continue
            start_t = time.perf_counter()
            if measured:
                rec.samples["lateness_ms"].append(
                    (start_t - t0 - due) * 1e3)
            client, row = int(ops.client[i]), int(ops.record[i])
            if ops.kind[i] == workgen.UPDATE:
                ver = self.put(client, row, ops.values[i])
                self.pending.append((row, ver, t0 + due, measured))
            else:
                floor = self.last_local.get((client, row), 0)
                ver, vals = self.get(client, row)
                if measured:
                    rec.samples["read_ms"].append(
                        (time.perf_counter() - t0 - due) * 1e3)
                    self.log.reads.append((row, ver, vals, floor))
            i += 1

    def _mark_visible(self) -> None:
        """Record the updates every replica now holds."""
        if not self.pending:
            return
        rows = np.array([p[0] for p in self.pending])
        vers = np.array([p[1] for p in self.pending])
        seen = self.held(rows) >= vers
        now = time.perf_counter()
        keep = []
        for p, ok in zip(self.pending, seen):
            if not ok:
                keep.append(p)
            elif p[3]:
                self.rec.samples["update_visible_ms"].append(
                    (now - p[2]) * 1e3)
                if self.rec.in_window:
                    self.rec.counts["updates_visible_in_window"] += 1
        self.pending = keep


def warm_joins(cluster: Cluster, max_rows: int) -> None:
    """Compile every ingest shape the window can use: a wire-decoded
    delta of r rows for every r up to ``max_rows`` (each r pads to its
    own grid), joined into a scratch copy of the first replica's store
    through the program's own decode and join. Their version (1) is
    older than every row's, so they change nothing; the results are
    dropped."""
    from repro.core.store import LatticeStore
    rep = cluster.reps[0]
    codec = rep.wire
    rng = np.random.default_rng(0)
    for r in range(1, max_rows + 1):
        rows = np.sort(rng.choice(cluster.n_rows, r, replace=False))
        keys, pos = np.divmod(rows, cluster.per)
        mapping = {}
        for k in np.unique(keys):
            sel = keys == k
            m = int(sel.sum())
            mapping[_key(int(k))] = cluster._ts.of({"w": cluster._sparse(
                cluster.per, pos[sel], np.zeros((m, cluster.width),
                                                np.float32),
                np.ones(m, np.int32))})
        frame = codec.encode_msg(("delta", LatticeStore.of(mapping), 1,
                                  None))
        _, delta, _, _ = codec.decode_msg(frame)
        cluster._resident.resident_of(rep.store.join(delta)).vals \
            .block_until_ready()


def prepare(cell, seed: int, rec) -> Cluster:
    """Set-up: the store from the seed, every replica on it, the ingest
    shapes compiled, and a short stretch of the cell's own traffic."""
    cfg, traffic = cell.config, cell.traffic
    width = int(cfg["record_floats"])
    n_rows = int(cfg["keys"]) * int(cfg["records_per_key"])
    warm_s = float(traffic["warm_seconds"])
    warm_ops = workgen.open_loop(traffic, n_rows, int(cfg["replicas"]),
                                 width, seed, warm_s, stream=1)
    cluster = Cluster(cfg, make_base(seed, n_rows, width), rec, seed)
    warm_joins(cluster, int(traffic["warm_join_rows"]))
    t0 = time.perf_counter()
    cluster.next_tick = 0.0
    cluster.serve(warm_ops, t0, warm_s + 60.0, measured=False,
                  done=lambda i: i >= len(warm_ops))
    # a read of a row each replica holds on the device (the warm traffic
    # may have read only rows still held on the host)
    for client in range(len(cluster.reps)):
        row = int(warm_ops.record[client % len(warm_ops)])
        cluster.put(client, row, warm_ops.values[0])
        cluster.get(client, row)
    return cluster


def measure(cluster: Cluster, ops: workgen.OpenLoopOps, seconds: float,
            drain_s: float) -> None:
    """The window, then the drain past its close: what is still due is
    served and every update is waited for (its latency counts the wait),
    at most ``drain_s``."""
    rec = cluster.rec
    bytes0 = cluster.sim.stats.bytes_sent
    t0 = rec.begin_window()
    cluster.next_tick = 0.0
    i = cluster.serve(ops, t0, seconds, measured=True)
    rec.end_window()
    rec.counts["wire_bytes"] = cluster.sim.stats.bytes_sent - bytes0
    # the joins the window's updates need: one row into every replica
    put = int(np.sum(ops.kind[:i] == workgen.UPDATE))
    rec.counts["join_bytes"] = work.scatter_join_bytes(
        put * len(cluster.reps), cluster.width)
    rec.reduce_trace()
    cluster.serve(ops, t0, seconds + drain_s, measured=True, start=i,
                  done=lambda j: j >= len(ops) and not any(
                      p[3] for p in cluster.pending))
    lost = sum(1 for p in cluster.pending if p[3])
    rec.attempted = len(ops)
    rec.failed = lost
    lat = rec.samples["lateness_ms"]
    rec.log(f"store: {len(ops)} ops in the window "
            f"({int(np.sum(ops.kind == workgen.UPDATE))} updates); {lost} "
            f"never visible; lateness p50/max {_pct(lat, 50)}/"
            f"{max(lat, default=None)} ms; first/last quarter mean "
            f"{_quarters(lat)} ms; ticks {len(rec.spans['tick'])}; visible "
            f"p50/90/95/99 {_pcts(rec.samples['update_visible_ms'])} ms; "
            f"read p50/90/95/99 {_pcts(rec.samples['read_ms'])} ms; updates "
            f"put in the window {put}, visible in it "
            f"{int(rec.counts['updates_visible_in_window'])}; wire bytes "
            f"{int(rec.counts['wire_bytes'])}")


def run(cell, seed: int, seconds: float, rec, devices) -> dict:
    traffic = cell.traffic
    cluster = prepare(cell, seed, rec)
    ops = workgen.open_loop(traffic, cluster.n_rows, len(cluster.reps),
                            cluster.width, seed, seconds, stream=2)
    measure(cluster, ops, seconds, float(traffic["drain_limit_s"]))
    ticks = 0
    while not cluster.converged() and ticks < CONVERGE_TICKS:
        cluster.tick()
        ticks += 1
    rec.read_memory_peak(devices)
    check_store(cluster, rec)
    return {}


def _pct(xs, q):
    from ..harness import percentile
    v = percentile(list(xs), q)
    return None if v is None else round(float(v), 3)


def _pcts(xs):
    return [_pct(xs, q) for q in (50, 90, 95, 99)]


def _quarters(xs):
    xs = list(xs)
    if len(xs) < 8:
        return None
    q = len(xs) // 4
    return (round(float(np.mean(xs[:q])), 3), round(float(np.mean(xs[-q:])), 3))


def check_store(cluster: Cluster, rec, block: int = 65536) -> None:
    """Every replica's converged columns against the reference, and every
    read of the window against what was written."""
    log = cluster.log
    rows = np.array(log.upd_row, np.int64)
    vers = np.array(log.upd_ver, np.int64)
    vals = (np.stack(log.upd_val) if log.upd_val
            else np.zeros((0, cluster.width), np.float32))
    ref_vals, ref_vers = store_ref.apply_updates(
        cluster.base_vals, cluster.base_vers.astype(np.int64), rows, vers,
        vals)
    apart = 0
    if not cluster.converged():
        held = [cluster.columns(r).vers_host[:cluster.n_rows]
                for r in cluster.reps]
        apart = int(np.sum(np.any([h != held[0] for h in held[1:]], axis=0)))
    rec.check("rows_apart", apart, 0)
    wrong = 0
    for r in cluster.reps:
        cache = cluster.columns(r)
        for s in range(0, cluster.n_rows, block):
            e = min(s + block, cluster.n_rows)
            v = np.asarray(cache.vals[s:e])
            ver = np.asarray(cache.vers[s:e])
            bad = (ver != ref_vers[s:e]) | np.any(
                v.view(np.int32) != ref_vals[s:e].view(np.int32), axis=1)
            wrong += int(bad.sum())
    rec.check("rows_wrong", wrong, 0)
    written = {(int(r), int(v)): x for r, v, x in zip(rows, vers, vals)}
    bad_reads = 0
    for row, ver, got, floor in log.reads:
        want = store_ref.value_of(cluster.base_vals, cluster.base_vers,
                                  written, row, ver)
        if (ver < floor or want is None
                or not np.array_equal(got.view(np.int32),
                                      np.asarray(want).view(np.int32))):
            bad_reads += 1
    rec.check("reads_wrong", bad_reads, 0)
    rec.check("updates_lost", rec.failed, 0)
