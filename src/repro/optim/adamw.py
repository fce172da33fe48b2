"""AdamW (decoupled weight decay) with mixed-precision discipline.

* params may be bf16; the optimizer keeps an fp32 master copy and fp32
  moments (12 bytes/param — the figure the roofline memory rows assume);
* gradients are cast to fp32 before moment updates;
* global-norm clipping in fp32;
* linear warmup → cosine decay schedule evaluated inside jit (step is a
  traced scalar, so one compiled train_step serves all steps).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp


@dataclass(frozen=True)
class AdamWConfig:
    lr: float = 3e-4
    b1: float = 0.9
    b2: float = 0.95
    eps: float = 1e-8
    weight_decay: float = 0.1
    clip_norm: float = 1.0
    warmup_steps: int = 100
    total_steps: int = 10_000
    min_lr_frac: float = 0.1


def lr_at_step(cfg: AdamWConfig, step: jax.Array) -> jax.Array:
    step = step.astype(jnp.float32)
    warm = cfg.lr * step / max(cfg.warmup_steps, 1)
    t = jnp.clip((step - cfg.warmup_steps)
                 / max(cfg.total_steps - cfg.warmup_steps, 1), 0.0, 1.0)
    cos = cfg.min_lr_frac * cfg.lr + (1 - cfg.min_lr_frac) * cfg.lr * \
        0.5 * (1.0 + jnp.cos(jnp.pi * t))
    return jnp.where(step < cfg.warmup_steps, warm, cos)


def init_opt_state(params: Any) -> Dict[str, Any]:
    # a fresh buffer even for f32 params: train steps donate the state
    f32 = lambda x: jnp.array(x, dtype=jnp.float32)
    return {
        "m": jax.tree_util.tree_map(lambda x: jnp.zeros(x.shape, jnp.float32),
                                    params),
        "v": jax.tree_util.tree_map(lambda x: jnp.zeros(x.shape, jnp.float32),
                                    params),
        "master": jax.tree_util.tree_map(f32, params),
        "step": jnp.zeros((), jnp.int32),
    }


def opt_state_pspecs(param_pspecs: Any) -> Dict[str, Any]:
    """Optimizer state shards exactly like the parameters (ZeRO)."""
    from jax.sharding import PartitionSpec as P
    return {"m": param_pspecs, "v": param_pspecs, "master": param_pspecs,
            "step": P()}


def _global_norm(tree: Any) -> jax.Array:
    return jnp.sqrt(sum(jnp.sum(jnp.square(x.astype(jnp.float32)))
                        for x in jax.tree_util.tree_leaves(tree)))


@jax.named_scope("adamw")
def adamw_update(params: Any, grads: Any, state: Dict[str, Any],
                 cfg: AdamWConfig) -> Tuple[Any, Dict[str, Any], Dict[str, jax.Array]]:
    step = state["step"] + 1
    gnorm = _global_norm(grads)
    scale = jnp.minimum(1.0, cfg.clip_norm / (gnorm + 1e-9))
    lr = lr_at_step(cfg, step)
    b1, b2 = cfg.b1, cfg.b2
    bc1 = 1.0 - b1 ** step.astype(jnp.float32)
    bc2 = 1.0 - b2 ** step.astype(jnp.float32)

    def upd(p_dtype_leaf, g, m, v, master):
        g = g.astype(jnp.float32) * scale
        m = b1 * m + (1 - b1) * g
        v = b2 * v + (1 - b2) * g * g
        mhat = m / bc1
        vhat = v / bc2
        new_master = master - lr * (mhat / (jnp.sqrt(vhat) + cfg.eps)
                                    + cfg.weight_decay * master)
        return new_master.astype(p_dtype_leaf.dtype), m, v, new_master

    flat_p, treedef = jax.tree_util.tree_flatten(params)
    flat_g = treedef.flatten_up_to(grads)
    flat_m = treedef.flatten_up_to(state["m"])
    flat_v = treedef.flatten_up_to(state["v"])
    flat_ma = treedef.flatten_up_to(state["master"])
    outs = [upd(p, g, m, v, ma) for p, g, m, v, ma
            in zip(flat_p, flat_g, flat_m, flat_v, flat_ma)]
    new_params = jax.tree_util.tree_unflatten(treedef, [o[0] for o in outs])
    new_state = {
        "m": jax.tree_util.tree_unflatten(treedef, [o[1] for o in outs]),
        "v": jax.tree_util.tree_unflatten(treedef, [o[2] for o in outs]),
        "master": jax.tree_util.tree_unflatten(treedef, [o[3] for o in outs]),
        "step": step,
    }
    return new_params, new_state, {"grad_norm": gnorm, "lr": lr}
