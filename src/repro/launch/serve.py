"""Serving driver: batched prefill → decode with KV caches.

Smoke-scale on CPU (reduced configs), production shapes via the dry-run.
Demonstrates the serving runtime end to end: batched requests, prefill,
iterative decode over ring caches (SWA archs keep O(window) state), and
greedy sampling. ``--replicate N`` additionally replicates the session
table as an ORMap δ-CRDT across N gateway replicas over a lossy network —
request metadata survives gateway failover with no coordinator (the
serving-side use of the paper).

``--ship-policy`` selects what the gateway gossip ships each round —
push policies (``bp+rr``, ``every:k``) and the pull exchange
(``digest-sync``, or the hybrid ``bp+rr+digest-sync:8``): gateways
periodically trade compact digest frames and receive back only the
session rows they are missing, so a reconnecting gateway catches up
without a full-state round.

``--sessions N`` is the scale-out version of the same story: N independent
session objects live in a keyed ``LatticeStore`` replicated across the
gateways, with rendezvous-hashed key ownership (``KeyOwnership`` +
``ShardByKey``) so each gateway only buffers and ships the sessions it
owns or replicates — bytes per anti-entropy round scale with a gateway's
shard, not with the whole fleet's session count. Any gateway accepts any
request (writes for non-owned keys forward to the owners through the
same gossip).

``--listen HOST:PORT --peers a,b,c`` leaves the simulator entirely: this
process becomes ONE member of a real gossip cluster (``repro.net``),
shipping the same δ-wire frames over actual UDP or TCP sockets. Each
process writes its share of the ``--sessions`` keys and gossips under
``--ship-policy`` until the cluster converges; ``--status-file`` publishes
a JSON heartbeat (semantic session fingerprint + byte counters) so an
external harness — the ``net`` benchmark suite, the CI ``net-smoke``
job — can assert cross-process convergence without any coordinator.
Socket mode requires the wire codec (``--no-wire`` is rejected) and
members may be named ``id@host:port`` to keep replica ids logical."""

from __future__ import annotations

import argparse
import random
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import ARCH_IDS, get_config
from repro.core import (AWORSet, Compose, MVRegister, NetConfig, ORMap,
                        POLICY_SPECS, Replica, Simulator, StoreReplica,
                        causal_policy_spec, converged, make_policy,
                        run_to_convergence)
from repro.launch.cache import enable_compile_cache
from repro.models import decode_step, init_model, prefill


def _policy_spec(s: str) -> str:
    try:                 # fail at arg parsing, not after the model ran
        return causal_policy_spec(s, "the session-table gossip")
    except ValueError as e:
        raise argparse.ArgumentTypeError(str(e))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen1.5-0.5b", choices=ARCH_IDS)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--replicate", type=int, default=0,
                    help="N gateway replicas for the δ-CRDT session table")
    ap.add_argument("--ship-policy", default="bp+rr", type=_policy_spec,
                    help="shipping policy for --replicate/--sessions "
                         f"gossip (e.g. {', '.join(POLICY_SPECS)}, "
                         "bp+rr+digest-sync:8)")
    ap.add_argument("--sessions", type=int, default=0,
                    help="N keyed session objects spread across the "
                         "gateways (LatticeStore + hash-sharded ownership; "
                         "implies 3 gateways unless --replicate is set)")
    ap.add_argument("--session-replication", type=int, default=2,
                    help="replicas per session key under --sessions")
    ap.add_argument("--session-ttl", type=float, default=None,
                    metavar="SECONDS",
                    help="key lifecycle for --sessions: every session "
                         "key expires SECONDS after its last write, and "
                         "the owner-driven reaper drops it to a tombstone "
                         "once the whole replica set acks the expiry "
                         "(repro.lifecycle). Works in the simulator (sim "
                         "time) and in socket mode (wall time, reap "
                         "frames over real UDP/TCP)")
    ap.add_argument("--no-wire", dest="wire", action="store_false",
                    help="gossip Python objects instead of binary δ-wire "
                         "frames (frames are the default: gateways move "
                         "bytes, and reported traffic is measured frame "
                         "lengths; incompatible with socket mode)")
    ap.add_argument("--listen", metavar="[ID@]HOST:PORT[@ZONE]",
                    default=None,
                    help="socket mode: gossip over real sockets as one "
                         "member of an OS-process cluster (repro.net); "
                         "requires --peers. An @ZONE suffix (zone or "
                         "region/zone) places this member in a failure "
                         "domain: byte accounting splits by link class "
                         "and gossip goes hierarchical (intra-zone push, "
                         "relay-batched cross-zone digest-sync)")
    ap.add_argument("--peers", metavar="[ID@]H:P[@ZONE],...", default=None,
                    help="socket mode: the other cluster members (zone "
                         "annotations must cover every member or none)")
    ap.add_argument("--transport", default="udp", choices=("udp", "tcp"),
                    help="socket-mode channel (UDP datagrams with "
                         "MTU splitting/batching, or TCP streams with "
                         "reconnect)")
    ap.add_argument("--udp-loss", type=float, default=0.0,
                    help="socket mode, UDP only: injected datagram loss "
                         "probability on the send path (reproducible "
                         "lossy-mesh runs over loopback)")
    ap.add_argument("--tick", type=float, default=0.1,
                    help="socket-mode anti-entropy period, seconds")
    ap.add_argument("--run-for", type=float, default=45.0,
                    help="socket mode: exit after this many seconds")
    ap.add_argument("--status-file", default=None,
                    help="socket mode: publish a JSON heartbeat "
                         "(fingerprint, key count, byte counters) here "
                         "for the external convergence harness")
    ap.add_argument("--metrics", action="store_true",
                    help="socket mode: export the observability registry "
                         "(repro.obs — replication lag, delta-buffer "
                         "depth, per-link-class byte rates, kernel "
                         "launches) on a loopback HTTP sidecar serving "
                         "Prometheus text at /metrics and JSON at "
                         "/metrics.json; --status-file heartbeats gain "
                         "the full snapshot")
    args = ap.parse_args()
    enable_compile_cache()

    if args.listen or args.peers:
        from repro.net import validate_net_args
        try:
            spec = validate_net_args(
                args.listen, args.peers, transport=args.transport,
                wire=args.wire, udp_loss=args.udp_loss,
                session_ttl=args.session_ttl)
        except ValueError as e:
            ap.error(str(e))
        _socket_sessions(args, spec)
        return

    cfg = get_config(args.arch, reduced=True)
    params, _ = init_model(cfg, jax.random.PRNGKey(args.seed))
    rng = np.random.default_rng(args.seed)
    b = args.batch
    max_len = args.prompt_len + args.gen
    if cfg.ssm is not None:
        # SSD prefill wants chunk-aligned prompt lengths
        args.prompt_len = max(cfg.ssm.chunk,
                              (args.prompt_len // cfg.ssm.chunk)
                              * cfg.ssm.chunk)
        max_len = args.prompt_len + args.gen

    if cfg.input_mode == "embeds":
        prompt = {"embeds": jnp.asarray(rng.normal(
            size=(b, args.prompt_len, cfg.d_model)).astype(np.float32),
            jnp.dtype(cfg.dtype))}
    elif cfg.input_mode == "tokens+prefix":
        tl = args.prompt_len - cfg.prefix_len
        assert tl > 0, "prompt shorter than the vision prefix"
        prompt = {
            "tokens": jnp.asarray(rng.integers(0, cfg.vocab, (b, tl)),
                                  jnp.int32),
            "prefix_embeds": jnp.asarray(rng.normal(
                size=(b, cfg.prefix_len, cfg.d_model)).astype(np.float32),
                jnp.dtype(cfg.dtype)),
        }
    else:
        prompt = {"tokens": jnp.asarray(
            rng.integers(0, cfg.vocab, (b, args.prompt_len)), jnp.int32)}

    t0 = time.time()
    prefill_jit = jax.jit(lambda p, x: prefill(cfg, p, x, max_len=max_len))
    logits, caches = prefill_jit(params, prompt)
    t_prefill = time.time() - t0

    decode_jit = jax.jit(lambda p, t, pos, c: decode_step(cfg, p, t, pos, c))
    tok = jnp.argmax(logits[:, -1], axis=-1)[:, None].astype(jnp.int32)
    generated = [np.asarray(tok)]
    t0 = time.time()
    for k in range(args.gen - 1):
        pos = jnp.full((b, 1), args.prompt_len + k, jnp.int32)
        if cfg.input_mode == "embeds":
            step_in = jnp.asarray(rng.normal(size=(b, 1, cfg.d_model))
                                  .astype(np.float32), jnp.dtype(cfg.dtype))
        else:
            step_in = tok
        logits, caches = decode_jit(params, step_in, pos, caches)
        tok = jnp.argmax(logits[:, -1], axis=-1)[:, None].astype(jnp.int32)
        generated.append(np.asarray(tok))
    dt = time.time() - t0
    toks = b * (args.gen - 1)
    print(f"[serve] arch={cfg.name} batch={b} prompt={args.prompt_len} "
          f"gen={args.gen}")
    print(f"  prefill: {t_prefill:.2f}s   decode: {dt:.2f}s "
          f"({toks / max(dt, 1e-9):.1f} tok/s on CPU smoke config)")
    print(f"  sample continuation (req 0): "
          f"{[int(g[0, 0]) for g in generated[:8]]}")

    if args.replicate:
        _replicated_sessions(args, b)
    if args.sessions:
        _keyed_sessions(args)


def _replicated_sessions(args, b: int) -> None:
    """Session table as ORMap(request → LWW status) across gateways,
    gossiped by the unified propagation runtime under --ship-policy."""
    wire = _wire_codec(args)
    sim = Simulator(NetConfig(loss=0.25, dup=0.1, seed=args.seed))
    ids = [f"gw{k}" for k in range(args.replicate)]
    nodes = [sim.add_node(Replica(i, ORMap.bottom(),
                                  [j for j in ids if j != i], causal=True,
                                  policy=make_policy(args.ship_policy),
                                  rng=random.Random(args.seed + k),
                                  wire=wire))
             for k, i in enumerate(ids)]
    for r in range(b):
        gw = nodes[r % len(nodes)]   # each request owned by one gateway →
        for status in ("queued", "prefilling", "decoding", "done"):
            # sequential writes per key: MVRegister holds a single value
            gw.operation(lambda X, r=r, s=status: X.apply_delta(
                gw.id, f"req{r}", MVRegister, "write_delta", s))
        sim.run_for(0.5)
    run_to_convergence(sim, nodes, interval=1.0)
    assert converged(nodes)
    table = nodes[0].X
    statuses = {k: next(iter(table.get_value(k, MVRegister).read()))
                for k in sorted(table.keys())}
    payload = sim.stats.payload_atoms()
    unit = "frame_bytes" if wire is not None else "payload_atoms"
    print(f"  [δ-CRDT] session table replicated over {args.replicate} "
          f"gateways (25% loss, policy={args.ship_policy}, "
          f"{unit}={payload}): {statuses}")
    assert all(v == "done" for v in statuses.values())


def _wire_codec(args):
    """The binary frame codec gateways gossip through (None = objects)."""
    if not args.wire:
        return None
    from repro.wire import WireCodec
    return WireCodec()


def _keyed_sessions(args) -> None:
    """N session objects in a keyed LatticeStore across gateways, with
    rendezvous-hash-sharded ownership: gossip ships each session only to
    the gateways that replicate it. Under ``--session-ttl`` each key
    also carries an expiry touched on every write, and the owner-driven
    reaper tombstones it once the whole replica set acks the expiry —
    the store *shrinks* again after the sessions complete."""
    from repro.sync import KeyOwnership, ShardByKey

    wire = _wire_codec(args)
    n_gw = max(args.replicate, 2) if args.replicate else 3
    ids = [f"gw{k}" for k in range(n_gw)]
    ownership = KeyOwnership(ids, replication=min(args.session_replication,
                                                  n_gw))
    sim = Simulator(NetConfig(loss=0.25, dup=0.1, seed=args.seed))
    nodes = [sim.add_node(StoreReplica(
        i, [j for j in ids if j != i], causal=True,
        policy=Compose(make_policy(args.ship_policy), ShardByKey(ownership)),
        rng=random.Random(args.seed + k), ownership=ownership, wire=wire,
        ttl=args.session_ttl or None))    # 0 ⇒ lifecycle off, like unset
        for k, i in enumerate(ids)]

    # gossip runs concurrently with ingest: register the periodic
    # anti-entropy (and GC) ticks before the first write
    for n in nodes:
        if args.session_ttl:
            from repro.lifecycle import ReaperProtocol
            ReaperProtocol(n, ownership, grace=1.0, retry=2.0)
        sim.every(1.0, n.on_periodic)
        sim.every(7.0, n.gc_deltas)

    for s in range(args.sessions):
        key = f"sess{s}"
        gw = nodes[s % len(nodes)]   # ingress gateway; may not own the key
        for status in ("queued", "prefilling", "decoding", "done"):
            gw.update(key, MVRegister, "write_delta", gw.id, status)
        if s % 8 == 7:
            sim.run_for(0.5)

    # then drive until every session's replica set agrees
    keys = [f"sess{s}" for s in range(args.sessions)]
    by_id = {n.id: n for n in nodes}

    def settled() -> bool:
        for key in keys:
            states = [by_id[w].get(key, MVRegister)
                      for w in ownership.owners(key)]
            if any(s != states[0] for s in states[1:]):
                return False
            if states[0].read() != frozenset({"done"}):
                return False
        return True

    t0 = sim.time
    while sim.time - t0 < 10_000:
        sim.run_for(2.0)
        if settled():
            break
    assert settled(), "sharded session store failed to settle"

    payload = sim.stats.payload_atoms()
    per_gw = {i: len([k for k in keys if ownership.replicates(i, k)])
              for i in ids}
    unit = "frame_bytes" if wire is not None else "payload_atoms"
    print(f"  [δ-CRDT store] {args.sessions} sessions sharded over "
          f"{n_gw} gateways (replication={ownership.replication}, 25% loss, "
          f"policy={args.ship_policy}+shard"
          f"{', binary δ-wire frames' if wire is not None else ''}): "
          f"all owner replicas settled to 'done'")
    print(f"    keys per gateway: {per_gw}   {unit}={payload}")

    if args.session_ttl:
        # every session saw its last write above; run the clock past the
        # TTL and let the acked reaper drain the store back down

        def all_reaped() -> bool:
            tombs = {i: by_id[i].X.tombstoned_keys() for i in ids}
            return all(key in tombs[w]
                       for key in keys for w in ownership.owners(key))

        t0 = sim.time
        while sim.time - t0 < args.session_ttl + 10_000:
            sim.run_for(5.0)
            if all_reaped():
                break
        tombs = {i: by_id[i].X.tombstoned_keys() for i in ids}
        reaped = {i: sum(1 for key in keys if key in tombs[i])
                  for i in ids}
        resident = {i: len(by_id[i].X.entries) for i in ids}
        assert all_reaped(), "sessions past their TTL were not reaped"
        print(f"  [lifecycle] ttl={args.session_ttl}s: all {args.sessions} "
              f"sessions expired and were reaped by their owners' ack "
              f"quorum; tombstones per gateway: {reaped}, resident "
              f"values left: {resident}")


def _session_fingerprint(replica, keys) -> str:
    """Semantic fingerprint of the session table: blake2b over the sorted
    ``(key, sorted read set)`` pairs. Representation-blind on purpose —
    a locally-written MVRegister and its wire-decoded columnar twin are
    semantically equal but structurally different objects, so hashing
    the *read values* is what lets N processes agree they converged."""
    import hashlib
    acc = hashlib.blake2b(digest_size=16)
    for key in sorted(keys):
        val = replica.get(key, MVRegister)
        reads = sorted(repr(v) for v in val.read()) if val is not None \
            else []
        acc.update(repr((key, reads)).encode("utf-8"))
    return acc.hexdigest()


def _socket_replica_factory(args, spec, topo):
    """The socket-mode replica factory: ``--ship-policy`` (composed with
    :class:`HierarchicalGossip` when the members carry zones), plus —
    under ``--session-ttl`` — full-replication key ownership and the
    acked reaper, so the tombstone quorum runs over real UDP/TCP.

    Ownership is the *whole static cluster* (replication = member
    count): every process derives the identical owner map from the same
    ``--peers`` list with no membership gossip, every replica holds
    every key (the cross-process fingerprint check stays meaningful),
    and a reap commits only once every member acked the expiry."""
    from repro.core.hiergossip import HierarchicalGossip
    from repro.core.propagation import stable_seed
    from repro.wire import WireCodec

    ownership = None
    if spec.session_ttl:
        from repro.sync import KeyOwnership
        ids = spec.cluster_ids
        ownership = KeyOwnership(ids, replication=len(ids), topology=topo)

    def make(node_id, neighbors):
        pol = make_policy(args.ship_policy)
        if topo is not None:
            pol = Compose(pol, HierarchicalGossip(topo))
        replica = StoreReplica(
            node_id, list(neighbors), causal=True, policy=pol,
            rng=random.Random(stable_seed(node_id)), wire=WireCodec(),
            ownership=ownership, ttl=spec.session_ttl)
        if spec.session_ttl:
            from repro.lifecycle import ReaperProtocol
            # grace/retry scale with the tick: proposals should survive
            # a couple of lost datagrams but not stall the reap for long
            ReaperProtocol(replica, ownership,
                           grace=max(2 * args.tick, 0.5),
                           retry=max(6 * args.tick, 1.0))
        return replica

    return make


def _socket_sessions(args, spec) -> None:
    """One member of a real socket gossip cluster (``repro.net``): write
    this process's share of the session keys, gossip frames until the
    run window closes, publish convergence heartbeats."""
    import asyncio

    async def run() -> None:
        from repro.net import GossipNode

        n_sessions = args.sessions if args.sessions else 12
        topo = spec.topology
        node = GossipNode(spec.node_id, spec.listen,
                          transport=spec.transport, peers=spec.peers,
                          replica_factory=_socket_replica_factory(
                              args, spec, topo),
                          topology=topo, tick=args.tick,
                          loss=args.udp_loss, seed=args.seed)
        await node.start()
        if args.metrics:
            node.export_metrics()
            maddr = await node.serve_metrics()
            print(f"[serve.net] {spec.node_id} metrics at "
                  f"http://{maddr}/metrics")
        ids = spec.cluster_ids
        rank, n = ids.index(spec.node_id), len(ids)
        mine = [s for s in range(n_sessions) if s % n == rank]
        print(f"[serve.net] {spec.node_id} listening on {node.addr} "
              f"({spec.transport}, policy={args.ship_policy}"
              f"{'+hier' if topo is not None else ''}, "
              f"{len(spec.peers)} peers, udp_loss={args.udp_loss}"
              f"{f', zone={node.zone}' if node.zone else ''}"
              f"{f', ttl={spec.session_ttl}s' if spec.session_ttl else ''}"
              f"); writing {len(mine)}/{n_sessions} sessions")
        for s in mine:
            for status in ("queued", "prefilling", "decoding", "done"):
                node.update(f"sess{s}", MVRegister, "write_delta",
                            node.id, status)
            await asyncio.sleep(args.tick / 4)   # interleave with gossip
        keys = [f"sess{s}" for s in range(n_sessions)]
        deadline = node.time + args.run_for
        while node.time < deadline:
            node.check_healthy()
            if args.status_file:
                _write_status(args.status_file, node, keys, n_sessions)
            await asyncio.sleep(min(0.25, args.tick))
        if args.status_file:
            _write_status(args.status_file, node, keys, n_sessions)
        print(f"[serve.net] {spec.node_id} done: "
              f"{len(node.X.keys())}/{n_sessions} keys resident, "
              f"frame_bytes_by_kind={node.stats.bytes_by_kind}, "
              f"{node.stats.summary()}")
        await node.stop()

    asyncio.run(run())


def _write_status(path: str, node, keys, n_sessions: int) -> None:
    """Atomic heartbeat write (tmp + rename) so the harness never reads
    a torn JSON."""
    import json
    import os
    resident = node.X.keys()
    done = all(k in resident and node.replica.get(k, MVRegister) is not None
               and node.replica.get(k, MVRegister).read()
               == frozenset({"done"}) for k in keys)
    payload = {
        "id": node.id,
        "keys": len(resident),
        "expect": n_sessions,
        "all_done": done,
        "fingerprint": _session_fingerprint(node.replica, keys),
        "bytes_by_kind": node.stats.bytes_by_kind,
        "stats": node.stats.summary(),
        # zoned observability: where this member sits and how many of
        # its bytes were local vs cross-zone (empty/None on a flat mesh)
        "zone": node.zone,
        "bytes_by_class": node.stats.bytes_by_class,
        "recv_bytes_by_class": node.stats.recv_bytes_by_class,
        "tombstones": len(node.X.tombstoned_keys()),
    }
    if node.metrics_registry is not None:
        # --metrics: the harness gets the whole registry without having
        # to scrape the sidecar (and the sidecar address in case it does)
        payload["metrics_addr"] = node.metrics_addr
        payload["metrics"] = node.metrics_registry.snapshot()
    tmp = f"{path}.tmp"
    with open(tmp, "w") as f:
        json.dump(payload, f)
    os.replace(tmp, path)


if __name__ == "__main__":
    main()
