"""Train / serve step factories.

``make_train_step``: loss (remat'd scan-over-layers) → grads → global-norm
clip → AdamW with fp32 master. Optional microbatch gradient accumulation
(``lax.scan`` over microbatches — activation memory ÷ n_micro at the cost
of serializing the per-microbatch collectives; a §Perf knob).

The factories close over the ModelConfig only; params/opt-state/batch come
in as arguments, so one jitted step serves the whole run and the dry-run
can lower it with ShapeDtypeStructs.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..models import decode_step as model_decode
from ..models import prefill as model_prefill
from ..models import train_loss_and_stats
from ..optim import AdamWConfig, adamw_update


@dataclass(frozen=True)
class TrainConfig:
    optimizer: AdamWConfig = AdamWConfig()
    microbatches: int = 1
    remat: bool = True


def make_train_step(cfg, tcfg: TrainConfig = TrainConfig()
                    ) -> Callable:
    """(params, opt_state, batch) → (params, opt_state, metrics). The
    metrics hold the model's counters (``train_loss_and_stats``) summed
    over the step's rows, on the device."""

    def loss_fn(params, batch):
        return train_loss_and_stats(cfg, params, batch, remat=tcfg.remat)

    grad_fn = jax.value_and_grad(loss_fn, has_aux=True)

    def train_step(params, opt_state, batch):
        if tcfg.microbatches == 1:
            (loss, stats), grads = grad_fn(params, batch)
        else:
            n = tcfg.microbatches

            def split(x):
                b = x.shape[0]
                assert b % n == 0
                return x.reshape((n, b // n) + x.shape[1:])

            micro = jax.tree_util.tree_map(split, batch)
            zero = jax.tree_util.tree_map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params)

            def acc_fn(carry, mb):
                loss_acc, g_acc = carry
                (loss, st), g = grad_fn(params, mb)
                g_acc = jax.tree_util.tree_map(
                    lambda a, x: a + x.astype(jnp.float32) / n, g_acc, g)
                return (loss_acc + loss / n, g_acc), st

            (loss, grads), st = jax.lax.scan(acc_fn, (0.0, zero), micro)
            stats = jax.tree_util.tree_map(lambda x: jnp.sum(x, 0), st)
        params, opt_state, om = adamw_update(params, grads, opt_state,
                                             tcfg.optimizer)
        metrics = {"loss": loss, **om, **stats}
        return params, opt_state, metrics

    return train_step


def make_prefill_fn(cfg, max_len: int) -> Callable:
    """(params, batch) → (next-token logits, caches)."""

    def prefill_fn(params, batch):
        return model_prefill(cfg, params, batch, max_len=max_len)

    return prefill_fn


def make_decode_fn(cfg) -> Callable:
    """(params, tokens, pos, caches) → (logits, caches). This is
    ``serve_step`` for the decode_* / long_* dry-run cells."""

    def decode_fn(params, tokens, pos, caches):
        return model_decode(cfg, params, tokens, pos, caches)

    return decode_fn
