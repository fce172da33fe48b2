"""End-to-end driver: train a ~100M-parameter decoder with δ-CRDT
machinery in the loop.

Two parts:

  (a) single-replica training with delta-interval checkpointing
      (crash-safe, idempotent restore) on a ~100M dense LM;
  (b) multi-pod local-SGD where pods gossip uniquely-dotted pseudo-gradient
      deltas over a 20%-loss network (Algorithm 2) — the paper's protocol
      carrying real training state.

CPU note: ~100M × a few hundred steps is hours on this 1-core container;
``--quick`` (default) runs a ~20M config × 60 steps so the loss curve is
visible in minutes. Pass ``--full`` for the ~100M × 300-step run.

Run:  PYTHONPATH=src python examples/train_delta_sync.py [--full]
"""

import argparse
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import (DeltaCheckpointStore, pytree_from_state,
                              state_from_pytree)
from repro.data import SyntheticLMStream
from repro.models import ModelConfig, init_model
from repro.optim import AdamWConfig
from repro.optim.adamw import init_opt_state
from repro.runtime import TrainConfig, make_train_step


def lm_config(full: bool) -> ModelConfig:
    if full:  # ~97M params
        return ModelConfig(name="lm-97m", family="dense", n_layers=10,
                           d_model=640, n_heads=10, n_kv_heads=10,
                           d_ff=2560, vocab=50_000, tie_embeddings=True,
                           act="swiglu", norm="rms", pos="rope",
                           dtype="float32")
    return ModelConfig(name="lm-21m", family="dense", n_layers=6,
                       d_model=384, n_heads=6, n_kv_heads=6,
                       d_ff=1536, vocab=16_000, tie_embeddings=True,
                       act="swiglu", norm="rms", pos="rope",
                       dtype="float32")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--steps", type=int, default=None)
    ap.add_argument("--ckpt", default="/tmp/repro_delta_ckpt")
    args = ap.parse_args()

    cfg = lm_config(args.full)
    steps = args.steps or (300 if args.full else 120)
    batch, seq = (8, 256) if args.full else (8, 128)
    total, _ = cfg.param_counts()
    print(f"model {cfg.name}: {total / 1e6:.0f}M params, "
          f"{steps} steps of batch {batch}x{seq}")

    stream = SyntheticLMStream(vocab=cfg.vocab, seq=seq, batch=batch, seed=3)
    params, _ = init_model(cfg, jax.random.PRNGKey(0))
    opt = init_opt_state(params)
    tcfg = TrainConfig(optimizer=AdamWConfig(
        lr=3e-3, warmup_steps=max(10, steps // 10), total_steps=steps),
        remat=False)
    step_fn = jax.jit(make_train_step(cfg, tcfg))

    store = DeltaCheckpointStore(args.ckpt)
    losses = []
    t0 = time.time()
    for step in range(steps):
        b = {k: jnp.asarray(v) for k, v in stream.batch_at(step).items()}
        params, opt, m = step_fn(params, opt, b)
        losses.append(float(m["loss"]))
        if step % 10 == 0 or step == steps - 1:
            print(f"  step {step:4d}  loss {losses[-1]:.4f}  "
                  f"({time.time() - t0:.0f}s)", flush=True)
        if (step + 1) % 20 == 0:
            # delta-interval checkpoint: snapshot every 3rd, delta otherwise
            full_state, spec = state_from_pytree(
                {"p": params, "o": opt}, chunk_size=65536, rank=0,
                lamport=step + 1)
            ck = store.seq + 1
            if ck % 3 == 0:
                store.save_snapshot(full_state, seq=ck)
            else:
                store.append_delta(full_state, seq=ck)
    want = 0.7 if args.full else 0.88   # quick mode: 120 CPU steps
    assert losses[-1] < losses[0] * want, "loss did not decrease"
    print(f"loss {losses[0]:.3f} -> {losses[-1]:.3f} "
          f"({(1 - losses[-1] / losses[0]):.0%} drop)")

    # crash/recovery: restore from the delta log and verify equality
    restored, seq = store.restore()
    full_state, spec = state_from_pytree({"p": params, "o": opt},
                                         chunk_size=65536, rank=0)
    back = pytree_from_state(restored, spec)
    same = all(np.allclose(np.asarray(a), np.asarray(b))
               for a, b in zip(jax.tree_util.tree_leaves(back["p"]),
                               jax.tree_util.tree_leaves(params)))
    print(f"restore from snapshot+deltas at ckpt-seq {seq}: "
          f"params identical = {same}")

    # (b) multi-pod delta gossip (smoke-scale; see repro.launch.train
    #     --mode delta for the full CLI)
    print("\nmulti-pod δ-CRDT local-SGD over a lossy network:")
    from repro.launch.train import run_delta

    class A:  # tiny args namespace
        arch, reduced = "qwen1.5-0.5b", True
        seq, batch, lr, seed = 64, 4, 1e-3, 0
        steps, local_steps, pods = 9, 3, 3
        net_loss, topk = 0.2, None
        # BP+RR: never echo a delta to its origin, never re-ship acked
        # state — same converged params, fewer gossip bytes
        ship_policy = "bp+rr"
    run_delta(A)


if __name__ == "__main__":
    main()
