"""End-to-end training driver.

Two modes (DESIGN.md §2):

* ``--mode sync``  — single-replica (or lockstep-SPMD) training with
  delta-interval checkpointing: snapshot every ``--snap-every`` steps,
  idempotent delta appends in between; crash at any point → restore =
  snapshot ⊔ deltas (Algorithm 2's durable-state discipline on disk).

* ``--mode delta`` — the paper's contribution end-to-end: ``--pods N``
  δ-CRDT replicas train local steps and gossip uniquely-dotted
  pseudo-gradient deltas over a lossy simulated network (loss/dup/reorder
  configurable); convergence is Prop. 1, not exactly-once delivery.

Both modes run the published widths of ``--arch``; ``--reduced`` swaps in
the same family's tiny smoke config (see examples/train_delta_sync.py for
a scripted version)."""

from __future__ import annotations

import argparse
import os
import random
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import (DeltaCheckpointStore, pytree_from_state,
                              state_from_pytree)
from repro.configs import ARCH_IDS, get_config
from repro.core import (NetConfig, POLICY_SPECS, Simulator,
                        causal_policy_spec, converged, make_policy,
                        run_to_convergence)
from repro.data import SyntheticLMStream
from repro.launch.cache import enable_compile_cache
from repro.models import init_model, train_loss
from repro.optim import AdamWConfig
from repro.runtime import TrainConfig, make_train_step
from repro.sync import DeltaSyncPod, TopKCompressor


def _init(cfg, seed):
    params, _ = init_model(cfg, jax.random.PRNGKey(seed))
    return params


def run_sync(args) -> None:
    cfg = get_config(args.arch, reduced=args.reduced)
    stream = SyntheticLMStream(vocab=cfg.vocab, seq=args.seq,
                               batch=args.batch, seed=args.seed)
    params = _init(cfg, args.seed)
    from repro.optim.adamw import init_opt_state
    opt_state = init_opt_state(params)
    tcfg = TrainConfig(optimizer=AdamWConfig(
        lr=args.lr, warmup_steps=max(10, args.steps // 20),
        total_steps=args.steps))
    step_fn = jax.jit(make_train_step(cfg, tcfg), donate_argnums=(0, 1))

    store = DeltaCheckpointStore(args.ckpt_dir) if args.ckpt_dir else None
    start_step = 0
    if store is not None and store.seq >= 0:
        state, seq = store.restore()
        if state.chunks:
            spec_state, spec = state_from_pytree(
                {"params": params, "opt": opt_state}, args.chunk, rank=0)
            restored = pytree_from_state(state, spec)
            params, opt_state = restored["params"], restored["opt"]
            start_step = int(np.asarray(opt_state["step"]))
            print(f"[restore] resumed at step {start_step} (ckpt seq {seq})")

    t0 = time.time()
    ck_seq = store.seq if store is not None else -1
    for step in range(start_step, args.steps):
        batch = {k: jnp.asarray(v) for k, v in
                 stream.batch_at(step).items()}
        params, opt_state, metrics = step_fn(params, opt_state, batch)
        if step % args.log_every == 0 or step == args.steps - 1:
            print(f"step {step:5d} loss {float(metrics['loss']):.4f} "
                  f"gnorm {float(metrics['grad_norm']):.3f} "
                  f"lr {float(metrics['lr']):.2e} "
                  f"({(time.time() - t0):.1f}s)", flush=True)
        if store is not None and (step + 1) % args.ckpt_every == 0:
            full, _spec = state_from_pytree(
                {"params": params, "opt": opt_state}, args.chunk, rank=0,
                lamport=step + 1)
            ck_seq += 1
            if ck_seq % args.snap_every == 0:
                store.save_snapshot(full, seq=ck_seq)
            else:
                store.append_delta(full, seq=ck_seq)  # idempotent join on restore
            store.gc(keep_snapshots=2)
    print(f"[done] {args.steps} steps in {time.time() - t0:.1f}s")


def make_delta_step(cfg, args):
    """The jitted inner step of ``--mode delta``. It donates params and
    optimizer state, so a caller keeps neither after a step."""
    tcfg = TrainConfig(optimizer=AdamWConfig(lr=args.lr,
                                             warmup_steps=5,
                                             total_steps=args.steps))
    return jax.jit(make_train_step(cfg, tcfg), donate_argnums=(0, 1))


def run_delta(args):
    """Train ``--pods`` delta-synced pods; returns ``(pods, losses)``
    with every local step's loss, in order."""
    cfg = get_config(args.arch, reduced=args.reduced)
    stream = SyntheticLMStream(vocab=cfg.vocab, seq=args.seq,
                               batch=args.batch, seed=args.seed)
    init_params = _init(cfg, args.seed)
    from repro.optim.adamw import init_opt_state
    step_jit = make_delta_step(cfg, args)
    losses = []

    def local_update(params, round_idx, pod_id):
        # K local steps on this pod's data shard (fresh opt state per round
        # — pseudo-gradient outer loop); the pod keeps ``params``, so the
        # donating step starts from a copy
        opt = init_opt_state(params)
        rank = int(pod_id.split("pod")[-1])
        p = jax.tree_util.tree_map(jnp.copy, params)
        for k in range(args.local_steps):
            b = stream.batch_at(round_idx * args.local_steps + k, rank=rank)
            p, opt, m = step_jit(p, opt, {k2: jnp.asarray(v)
                                          for k2, v in b.items()})
            losses.append(float(m["loss"]))
        print(f"  [{pod_id}] round {round_idx} loss "
              f"{losses[-1]:.4f}", flush=True)
        return p

    sim = Simulator(NetConfig(loss=args.net_loss, dup=0.1, seed=args.seed))
    ids = [f"pod{k}" for k in range(args.pods)]
    policy_spec = getattr(args, "ship_policy", "all")
    pods = [sim.add_node(DeltaSyncPod(
        i, [j for j in ids if j != i], init_params, local_update,
        num_pods=args.pods,
        compressor=(TopKCompressor(args.topk) if args.topk else None),
        rng=random.Random(args.seed + n),
        policy=make_policy(policy_spec)))
        for n, i in enumerate(ids)]

    rounds = max(1, args.steps // args.local_steps)
    for r in range(rounds):
        for p in pods:
            p.do_round()
        sim.run_for(5.0)  # anti-entropy gossip between rounds
    run_to_convergence(sim, pods, interval=1.0, max_time=50_000)
    assert converged(pods), "pods failed to converge"
    payload = sim.stats.payload_atoms()
    print(f"[done] {rounds} rounds × {args.local_steps} local steps on "
          f"{args.pods} pods over a lossy network (loss={args.net_loss}, "
          f"ship-policy={policy_spec}, payload_atoms={payload}); "
          f"all pods converged to identical outer params "
          f"({len(pods[0].X.dots)} dots merged)")
    return pods, losses


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen1.5-0.5b", choices=ARCH_IDS)
    ap.add_argument("--mode", default="sync", choices=["sync", "delta"])
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--log-every", type=int, default=10)
    # checkpointing
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=10)
    ap.add_argument("--snap-every", type=int, default=5,
                    help="every Nth checkpoint is a full snapshot")
    ap.add_argument("--chunk", type=int, default=65536)
    # delta mode
    ap.add_argument("--pods", type=int, default=2)
    ap.add_argument("--local-steps", type=int, default=5)
    ap.add_argument("--net-loss", type=float, default=0.2)
    ap.add_argument("--topk", type=float, default=None,
                    help="top-k compression rate (e.g. 0.1)")
    def _policy_spec(s):
        try:             # fail at arg parsing, not after N training steps
            return causal_policy_spec(s, "delta-mode gossip")
        except ValueError as e:
            raise argparse.ArgumentTypeError(str(e))

    ap.add_argument("--ship-policy", default="all", type=_policy_spec,
                    help="delta-mode gossip shipping policy "
                         f"(e.g. {', '.join(POLICY_SPECS)})")
    args = ap.parse_args()
    enable_compile_cache()
    if args.mode == "sync":
        run_sync(args)
    else:
        run_delta(args)


if __name__ == "__main__":
    main()
