#!/usr/bin/env python3
"""Run the system's main path once on one TPU chip and check its results.

    python3 chip_smoke.py [--seed N]

Two phases run in this one process, one after the other:

1. **Store.** Three device-resident ``StoreReplica``s gossip through the
   wire codec (decode-to-device) over the lossy ``Simulator``: 10% loss,
   10% duplication. Each starts from the same bulk-loaded base store of
   65,536 keys, each a ``TensorState`` of 16 chunks × 256 f32, so every
   replica holds 1 GiB of value columns on the device. Every round each
   replica writes sparse updates to about 1% of the keys, drawn
   zipfian(0.99) from ``--seed``; after the write rounds the replicas run
   anti-entropy to convergence. The converged columns must be
   bit-identical to a numpy reference (the per-chunk highest version over
   the base store and the write log), the launch counters must show the
   compiled (Mosaic) join kernels, and a ≥1M-dot causal join must agree
   with the frozenset oracle through ``missing_mask``'s jax path. The
   phase drops its device buffers before the next one starts.
2. **Train.** ``repro.launch.train.run_delta`` trains qwen1.5-0.5b at its
   published widths: 2 pods, 2 outer rounds × 2 local steps, batch 8 ×
   seq 512, over the lossy simulated gossip. The step's memory is
   reckoned first from ``compiled.memory_analysis()``; if it would not
   fit, outer rounds drop to 1, then the sequence halves — never a width
   — and each cut is printed. The pods must converge, every loss must be
   finite, and the outer params must equal ``init + Σ updates / P``
   summed straight from the pods' contributed deltas.

Each phase prints its wall time (compile time split out), the device's
``peak_bytes_in_use`` so far, and kernel launches by name and mode. These
lines are a bring-up record, not a benchmark. The last line is exactly
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
On any other platform, or when any check fails, the script exits non-zero
and prints no such line.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time
import traceback
from types import SimpleNamespace

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

import jax  # noqa: E402
import numpy as np  # noqa: E402

# ---------------------------------------------------------------------------
# Store phase
# ---------------------------------------------------------------------------

RANK_BITS = 10          # version = (lamport << RANK_BITS) | writer rank


def _zipf_keys(rng, n_keys: int, draws: int, theta: float = 0.99):
    """``draws`` key indices from a bounded zipfian(theta) over
    ``n_keys``, hot ranks scattered over the key space by a fixed
    permutation (YCSB's scrambled zipfian)."""
    w = 1.0 / np.arange(1, n_keys + 1, dtype=np.float64) ** theta
    cdf = np.cumsum(w) / w.sum()
    ranks = np.minimum(np.searchsorted(cdf, rng.random(draws)), n_keys - 1)
    return np.random.default_rng(0).permutation(n_keys)[ranks]


def _write_batch(rng, n_keys: int, chunks: int, width: int,
                 write_frac: float, version: int):
    """One replica's writes for one round: unique keys, 1+ chunks each.
    Returns ``(keys, rows [r] global chunk rows sorted, vals [r, width],
    vers [r])``."""
    keys = np.unique(_zipf_keys(rng, n_keys,
                                max(1, int(n_keys * write_frac))))
    pick = rng.random((keys.size, chunks)) < 2.0 / chunks
    pick[np.arange(keys.size), rng.integers(0, chunks, keys.size)] = True
    k_idx, c_idx = np.nonzero(pick)
    rows = keys[k_idx] * chunks + c_idx
    vals = rng.standard_normal((rows.size, width), dtype=np.float32)
    return keys, rows, vals, np.full(rows.size, version, np.int32)


def _delta_store(keys, rows, vals, vers, chunks: int, lamport: int):
    from repro.core.store import LatticeStore
    from repro.core.tensor_lattice import TensorState, sparse_chunks
    bounds = np.searchsorted(rows // chunks, keys, side="left")
    ends = np.r_[bounds[1:], rows.size]
    return LatticeStore.of({
        f"k{k:06d}": TensorState.of({"w": sparse_chunks(
            chunks, rows[s:e] - k * chunks, vals[s:e], vers[s:e])},
            lamport=lamport)
        for k, s, e in zip(keys.tolist(), bounds.tolist(), ends.tolist())})


def _kernel_check(seed: int, n: int = 4096, width: int = 256,
                  r: int = 300) -> None:
    """Each store kernel, as the store calls it (compiled on the chip),
    against its jnp oracle on a small seeded input: names the kernel at
    fault before the store-level check would."""
    import jax.numpy as jnp
    from repro.kernels import ops, ref

    rng = np.random.default_rng(seed)
    vals = lambda m: jnp.asarray(rng.standard_normal((m, width),
                                                     dtype=np.float32))
    vers = lambda m: jnp.asarray(rng.integers(0, 50, m).astype(np.int32))
    av, ar, bv, br = vals(n), vers(n), vals(n), vers(n)
    bv = jnp.where((ar == br)[:, None], av, bv)   # equal versions, equal rows
    ma, ss = ref.chunk_digest_ref(av)
    idx = jnp.asarray(rng.permutation(n)[:r].astype(np.int32))
    dv, dr = vals(r), vers(r)
    interp = not ops.use_pallas_default()
    runs = {
        "delta_join": (ops.delta_join(av, ar, bv, br, interpret=interp),
                       ref.delta_join_ref(av, ar, bv, br)),
        "fused_join_digest": (ops.fused_join_digest(av, ar, bv, br),
                              ref.fused_join_digest_ref(av, ar, bv, br)),
        "chunk_digest": (ops.chunk_digest(av, interpret=interp), (ma, ss)),
        "scatter_join": (ops.scatter_join(av, ar, ma, ss, idx, dv, dr),
                         ref.scatter_join_ref(av, ar, ma, ss, idx, dv, dr)),
    }
    for name, (got, want) in runs.items():
        for g, w in zip(got, want):
            g, w = np.asarray(g), np.asarray(w)
            ok = (np.allclose(g, w, rtol=1e-5) if g.dtype == np.float32
                  and g.ndim == 1 else np.array_equal(g, w))
            assert ok, f"{name} disagrees with its oracle"


def _dots_check(seed: int, per_rid: int) -> dict:
    """A causal join of 4·``per_rid`` dots through ``causal_join_cols``
    (``missing_mask`` on its auto path: jax on the chip), checked against
    the frozenset oracle and against ``missing_mask``'s numpy path."""
    from repro.core.dotcols import (SEQ_BITS, CausalContextCols, DotSetCols,
                                    causal_join_cols, missing_mask)
    from repro.core.dots import causal_join

    rng = np.random.default_rng(seed)
    rids = ("a", "b", "c", "d")
    n = per_rid

    def packed(r, seqs):
        return (np.int64(r) << SEQ_BITS) | np.asarray(seqs, np.int64)

    def run(lo, hi):
        return np.arange(lo, hi + 1, dtype=np.int64)

    # cloud dots: sparse seqs past the vv prefix, never contiguous with it
    cloud_c = np.unique(rng.integers(n // 2 + 2, n, n // 64)) | 1
    cloud_c = cloud_c[cloud_c > n // 2 + 1]
    sa = DotSetCols(rids, np.concatenate([packed(0, run(1, n)),
                                          packed(1, run(1, n))]))
    ca = CausalContextCols(rids, np.array([n, n, n // 2, 0], np.int64),
                           np.unique(packed(2, cloud_c)))
    sb = DotSetCols(rids, np.concatenate([packed(0, run(1, n // 4)),
                                          packed(2, run(1, n)),
                                          packed(3, run(1, n))]))
    cb = CausalContextCols(rids, np.array([n // 4, n // 5, n, n], np.int64),
                           np.zeros(0, np.int64))
    dots = sa.packed.size + sb.packed.size
    sc, cc = causal_join_cols(sa, ca, sb, cb)
    so, co = causal_join(sa.to_obj(), ca.to_obj(), sb.to_obj(), cb.to_obj())
    assert sc.to_obj() == so and cc.to_obj() == co, \
        "columnar causal join diverged from the frozenset oracle"
    for vv, cloud, col in ((cb.vvcol, cb.cloudcol, sa.packed),
                           (ca.vvcol, ca.cloudcol, sb.packed)):
        got = missing_mask(vv, cloud, col, backend="jax")
        assert np.array_equal(got, missing_mask(vv, cloud, col,
                                                backend="numpy")), \
            "missing_mask: jax path != numpy path"
    return {"dots": int(dots), "joined_dots": int(sc.packed.size)}


def store_phase(seed: int, *, n_keys: int = 65536, chunks: int = 16,
                width: int = 256, rounds: int = 5, write_frac: float = 0.01,
                dots_per_rid: int = 250_000) -> dict:
    """The store phase at the given scale; returns its counts. Raises on
    any failed check."""
    from repro.core import NetConfig, Simulator, StoreReplica
    from repro.core.digest import store_digest
    from repro.core.store import LatticeStore
    from repro.core.tensor_lattice import ChunkedTensor, TensorState
    from repro.kernels import resident
    from repro.wire.frames import WireCodec

    _kernel_check(seed)
    rng = np.random.default_rng(seed)
    n_rows = n_keys * chunks
    base_vals = rng.standard_normal((n_rows, width), dtype=np.float32)
    base_vers = np.full(n_rows, 1 << RANK_BITS, np.int32)   # lamport 1
    base = LatticeStore.of({
        f"k{k:06d}": TensorState.of({"w": ChunkedTensor(
            base_vals[k * chunks:(k + 1) * chunks],
            base_vers[k * chunks:(k + 1) * chunks])}, lamport=1)
        for k in range(n_keys)})

    # Algorithm 2 (causal): lost intervals are re-sent until acked. The
    # basic mode would ship the whole store to a peer it owes nothing new.
    ids = ["r0", "r1", "r2"]
    sim = Simulator(NetConfig(loss=0.1, dup=0.1, seed=seed))
    wire = WireCodec(to_device=True)
    reps = [sim.add_node(StoreReplica(i, [j for j in ids if j != i],
                                      causal=True, wire=wire, resident=True))
            for i in ids]
    for r in reps:          # bulk load: every replica's durable state
        r.recover((LatticeStore(base.entries, base.life), 0))
        resident.ensure(r.store)
    del base
    resident_bytes = [resident.resident_of(r.store).nbytes_device()
                      for r in reps]

    ref_vals, ref_vers = base_vals.copy(), base_vers.copy()
    writes = 0
    for t in range(rounds):
        for rank, rep in enumerate(reps, start=1):
            lamport = t + 2
            keys, rows, vals, vers = _write_batch(
                rng, n_keys, chunks, width, write_frac,
                (lamport << RANK_BITS) | rank)
            delta = _delta_store(keys, rows, vals, vers, chunks, lamport)
            rep.operation(lambda S, d=delta: d)
            take = vers > ref_vers[rows]              # the numpy reference
            ref_vals[rows[take]] = vals[take]
            ref_vers[rows[take]] = vers[take]
            writes += rows.size
        for rep in reps:
            rep.on_periodic()
        sim.run_for(2.0)

    def agree():
        d0, *rest = [store_digest(r.store) for r in reps]
        return all(d == d0 for d in rest)

    gossip = 0
    while not agree():
        assert gossip < 200, "replicas did not converge"
        for rep in reps:
            rep.on_periodic()
            rep.gc_deltas()
        sim.run_for(2.0)
        gossip += 1

    for r in reps:
        cache = resident.resident_of(r.store)
        assert cache is not None, f"{r.id}: store left the device"
        vals = np.asarray(cache.vals)[:n_rows]
        vers = np.asarray(cache.vers)[:n_rows]
        assert np.array_equal(vers, ref_vers), f"{r.id}: versions differ"
        assert np.array_equal(vals.view(np.int32), ref_vals.view(np.int32)), \
            f"{r.id}: values differ from the reference"
        assert np.array_equal(cache.vers_host[:n_rows], ref_vers)
    # joining two converged resident replicas: one fused launch, no change
    both = reps[0].store.join(reps[1].store)
    assert np.array_equal(np.asarray(resident.resident_of(both).vals)[:n_rows]
                          .view(np.int32), ref_vals.view(np.int32))
    del both

    dots = _dots_check(seed, dots_per_rid)
    return {"replicas": len(reps), "keys": n_keys,
            "resident_bytes_per_replica": resident_bytes,
            "write_rounds": rounds, "gossip_rounds_to_converge": gossip,
            "rows_written": writes, "sim_time": sim.time, **dots}


# ---------------------------------------------------------------------------
# Train phase
# ---------------------------------------------------------------------------

def _step_bytes(cfg, args) -> tuple:
    """``(bytes the donating train step needs, bytes of one params
    copy)``, from ``compiled.memory_analysis()`` of the step at
    ``args``' shape."""
    from repro.data import SyntheticLMStream
    from repro.launch.train import make_delta_step
    from repro.models import init_model
    from repro.optim.adamw import init_opt_state

    params = jax.eval_shape(
        lambda: init_model(cfg, jax.random.PRNGKey(0))[0])
    opt = jax.eval_shape(init_opt_state, params)
    batch = SyntheticLMStream(vocab=cfg.vocab, seq=args.seq,
                              batch=args.batch, seed=0).batch_at(0)
    batch = {k: jax.ShapeDtypeStruct(v.shape, v.dtype)
             for k, v in batch.items()}
    m = make_delta_step(cfg, args).lower(params, opt, batch) \
        .compile().memory_analysis()
    step = (m.argument_size_in_bytes + m.output_size_in_bytes
            - m.alias_size_in_bytes + m.temp_size_in_bytes)
    one = sum(x.size * x.dtype.itemsize
              for x in jax.tree_util.tree_leaves(params))
    return step, one


def plan_training(args, bytes_limit: int, log=print) -> list:
    """Fit ``args`` to ``bytes_limit``: the donating step plus what the
    pods hold beside it at the last local step (init, the pod's base, and
    every outer round's delta but its own). Cuts outer rounds to 1, then
    halves the sequence; never a width. Returns the cuts made."""
    from repro.configs import get_config
    cfg = get_config(args.arch, reduced=args.reduced)
    cuts = []
    while True:
        step, one = _step_bytes(cfg, args)
        rounds = args.steps // args.local_steps
        need = step + one * (args.pods * rounds + 1)
        log(f"[train] memory plan: step {step} B + params {one} B x "
            f"{args.pods * rounds + 1} = {need} B of {bytes_limit} B "
            f"(seq {args.seq}, outer rounds {rounds})")
        if need <= bytes_limit:
            return cuts
        if rounds > 1:
            cuts.append(f"outer rounds {rounds} -> 1")
            args.steps = args.local_steps
        elif args.seq > 64:
            cuts.append(f"seq {args.seq} -> {args.seq // 2}")
            args.seq //= 2
        else:
            raise AssertionError("the training phase does not fit the chip")


def train_phase(args) -> dict:
    """``run_delta`` with ``args``, then the convergence, loss and
    outer-params checks; returns its counts. Raises on any failure."""
    from repro.launch.train import run_delta

    pods, losses = run_delta(args)
    n_pods = len(pods)
    rounds = args.steps // args.local_steps
    assert len(losses) == n_pods * rounds * args.local_steps
    assert all(np.isfinite(losses)), f"non-finite loss in {losses}"
    for p in pods:
        assert len(p.X.dots) == n_pods * rounds, f"{p.id}: missing dots"

    # reference: init + Σ updates / P, from each pod's own contributions,
    # summed in f32 on the host; compared leaf by leaf to every pod
    init = jax.tree_util.tree_leaves(pods[0].outer.init)
    own = [upd for p in pods for (producer, _), upd in p.X.dots
           if producer == p.id]
    assert len(own) == n_pods * rounds
    own = [jax.tree_util.tree_leaves(u) for u in own]
    outer = [jax.tree_util.tree_leaves(p.params()) for p in pods]
    worst = 0.0
    for li, x0 in enumerate(init):
        ups = [np.asarray(u[li], np.float32) for u in own]
        ref = np.asarray(x0, np.float32) + sum(ups) / n_pods
        got = [np.asarray(o[li]) for o in outer]
        for g in got[1:]:
            assert np.array_equal(g.view(np.uint8), got[0].view(np.uint8)), \
                "pods hold different outer params"
        out = got[0].astype(np.float32)
        # rounding to the params' dtype: one ulp of the result (the device
        # may round an f32 intermediate first), plus half an ulp of each
        # of the len(own) - 1 partial sums of the updates, each at most
        # Σ|u|; the last factor bounds how those roundings compound
        eps = float(jax.numpy.finfo(got[0].dtype).eps)
        tol = (1 + eps) ** len(own) * (
            eps * np.maximum(np.abs(out), np.abs(ref))
            + eps / 2 * (len(own) - 1) * sum(np.abs(u) for u in ups)
            / n_pods)
        err = np.abs(out - ref)
        bad = np.argmax(err - tol)
        assert err.flat[bad] <= tol.flat[bad], (
            f"outer params leaf {li}{got[0].shape}[{bad}]: {out.flat[bad]!r} "
            f"vs reference {ref.flat[bad]!r} (tolerance {tol.flat[bad]!r})")
        worst = max(worst, float((err / np.maximum(tol, 1e-30)).max()))
    return {"pods": n_pods, "outer_rounds": rounds,
            "local_steps": args.local_steps, "losses": losses,
            "dots": len(pods[0].X.dots),
            "worst_error_over_tolerance": worst}


# ---------------------------------------------------------------------------
# Driver
# ---------------------------------------------------------------------------

class _Compiles:
    """Seconds spent tracing, lowering and compiling, from JAX's own
    compile events."""

    def __init__(self):
        self.secs = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, secs: float, **_) -> None:
        if event.startswith("/jax/core/compile/"):
            self.secs += secs


def _phase(name: str, fn, device, compiles: _Compiles, log) -> dict:
    """Run one phase and log its record; returns the record."""
    from repro.kernels import ops
    launches = dict(ops.counters.by_kernel)
    c0, t0 = compiles.secs, time.perf_counter()
    out = fn()
    wall = time.perf_counter() - t0
    stats = device.memory_stats() or {}
    out.update({
        "wall_s": wall, "compile_s": compiles.secs - c0,
        "peak_bytes_in_use": stats.get("peak_bytes_in_use"),
        "bytes_in_use": stats.get("bytes_in_use"),
        "launches": {k: v - launches.get(k, 0)
                     for k, v in ops.counters.by_kernel.items()
                     if v != launches.get(k, 0)}})
    log(f"[{name}] " + json.dumps(out))
    return out


def _store_launches(out: dict) -> dict:
    """The store phase ran its kernels compiled on the chip."""
    got = out["launches"]
    for kernel in ("scatter_join:compiled", "missing_mask:xla"):
        assert got.get(kernel), f"no {kernel} launch"
    assert (got.get("fused_join_digest:compiled")
            or got.get("delta_join:compiled")), "no compiled join launch"
    return out


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    device = jax.devices()[0]
    if device.platform != "tpu":
        sys.exit(f"chip_smoke: no TPU found (JAX reports platform "
                 f"{device.platform!r}); this check runs only on the chip")

    from repro.launch.cache import enable_compile_cache
    from repro.kernels import ops
    log = lambda s: print(s, flush=True)
    log(f"[device] {device.device_kind} x{len(jax.devices())}; compile "
        f"cache {enable_compile_cache()}")
    assert ops.use_pallas_default(), "Pallas kernels are off on this chip"
    compiles = _Compiles()

    failed = []

    def run(name, fn, check=lambda out: out):
        # a failed phase fails the run; the next phase still runs, so
        # one call on the chip reports on both
        try:
            check(_phase(name, fn, device, compiles, log))
        except Exception:
            traceback.print_exc()
            failed.append(name)
        gc.collect()                       # drop the phase's buffers
        log(f"[{name}] released; bytes_in_use "
            f"{(device.memory_stats() or {}).get('bytes_in_use')}")

    run("store", lambda: store_phase(args.seed), _store_launches)

    def train():
        targs = SimpleNamespace(
            arch="qwen1.5-0.5b", reduced=False, pods=2, steps=4,
            local_steps=2, batch=8, seq=512, lr=3e-4, seed=args.seed,
            net_loss=0.2, topk=None, ship_policy="all")
        limit = (device.memory_stats() or {}).get("bytes_limit")
        assert limit, "the device reports no memory limit"
        cuts = plan_training(targs, limit, log)
        log(f"[train] cuts: {cuts or 'none'}")
        return {**train_phase(targs), "cuts": cuts, "seq": targs.seq,
                "batch": targs.batch}

    run("train", train)
    if failed:
        sys.exit(f"chip_smoke: failed phases: {', '.join(failed)}")
    print(json.dumps({"ok": True, "device": {
        "platform": device.platform, "kind": device.device_kind,
        "count": len(jax.devices())}}))


if __name__ == "__main__":
    main()
