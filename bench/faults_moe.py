"""Faults planted under the MoE training cell's timed path, to show that
its comparison fails when the routing or the held-expert layer is wrong,
and the cell's control: the reference at float8 in the step's place.
Each takes the cell and returns a context manager that patches the
program for one run, as ``bench/faults.py`` does for the other cells.
"""

from __future__ import annotations

import dataclasses
import functools
import math

from .faults import _patched, _wrap_step


def _moe():
    from repro.models import moe
    return moe


def moe_bias_ignored(cell):
    """Experts are chosen on the scores alone: the selection bias is
    left out."""
    moe = _moe()
    inner = moe._route

    def route(p, cfg, xf):
        import jax.numpy as jnp
        return inner({**p, "bias": jnp.zeros_like(p["bias"])}, cfg, xf)
    return _patched(moe, "_route", route)


def moe_not_renormalised(cell):
    """The chosen experts' weights are not divided by their sum."""
    moe = _moe()
    inner = moe._route

    def route(p, cfg, xf):
        return inner(p, dataclasses.replace(cfg, moe=dataclasses.replace(
            cfg.moe, norm_topk=False)), xf)
    return _patched(moe, "_route", route)


def moe_capacity_drops(cell):
    """Each held expert takes at most 1.25 times its mean share of the
    pairs; the pairs past that are dropped, as a capacity-bounded
    dispatch drops them."""
    import jax.numpy as jnp
    moe = _moe()
    inner = moe._dispatch

    def dispatch(expert_ids, first, n):
        flat = expert_ids.reshape(-1)
        num = int(cell.config["router_experts"])
        cap = math.ceil(1.25 * flat.shape[0] / num)
        order = jnp.argsort(flat, stable=True)
        sorted_ids = flat[order]
        start = jnp.searchsorted(sorted_ids, sorted_ids, side="left")
        rank = jnp.zeros_like(flat).at[order].set(
            jnp.arange(flat.shape[0]) - start)
        kept = jnp.where(rank < cap, flat, -1)       # an expert held nowhere
        return inner(kept.reshape(expert_ids.shape), first, n)
    return _patched(moe, "_dispatch", dispatch)


def moe_wrong_held(cell):
    """The layer computes the pairs of the next range of experts with
    the weights of its own."""
    moe = _moe()
    inner = moe._apply_moe_held

    def held(p, cfg, x):
        first, n = cfg.moe.held
        shifted = ((first + n) % cfg.moe.num_experts, n)
        return inner(p, dataclasses.replace(cfg, moe=dataclasses.replace(
            cfg.moe, held=shifted)), x)
    return _patched(moe, "_apply_moe_held", held)


def moe_control_fp8(cell):
    """The control: the reference model and its AdamW, with every
    contraction in float8 (``qwen_ref.round_fp8``; the configuration
    trains in bfloat16), in the program step's place. It keeps the
    program's parameter and optimizer-state trees, donates its
    parameters as the program's step does, and reports the reference's
    routing counts as the step's counters."""
    import jax
    from .drivers.delta_train_moe import model_config, param_names
    from .reference import kanana_ref, qwen_ref
    names = param_names(model_config(cell.config))
    first, held = (int(x) for x in cell.config["held_experts"])

    def make(step, args):
        ref = kanana_ref.AdamW(
            cell.config, cell.config["train"]["optimizer"], args.steps,
            rounding=qwen_ref.round_fp8,
            micro_batches=int(cell.traffic["reference_micro_batches"]))

        def fp8_step(p, opt, batch):
            treedef = jax.tree_util.tree_structure(opt["master"])

            def named(tree):
                return dict(zip(names, jax.tree_util.tree_leaves(tree)))

            def tree(d):
                return treedef.unflatten([d[n] for n in names])

            dtypes = [x.dtype for x in jax.tree_util.tree_leaves(p)]
            for x in jax.tree_util.tree_leaves(p):
                x.delete()
            t = int(opt["step"]) + 1
            master = named(opt["master"])
            loss, g, (load, _) = ref.grad(master, batch["tokens"],
                                          batch["labels"])
            master, m, v, _ = ref.update(master, g, named(opt["m"]),
                                         named(opt["v"]), t)
            new_p = treedef.unflatten(_cast()([master[n] for n in names],
                                              tuple(dtypes)))
            return new_p, {"m": tree(m), "v": tree(v), "master": tree(master),
                           "step": opt["step"] + 1}, {
                "loss": loss, "moe_load": load,
                "moe_rows": load[:, first:first + held]}
        return fp8_step
    return _wrap_step(make)


@functools.lru_cache(maxsize=None)
def _cast():
    """Copies of a list of arrays in the given dtypes, in one jitted call:
    the next step donates them, and the master kept must outlive that."""
    import jax
    import jax.numpy as jnp
    return jax.jit(lambda xs, dtypes: [jnp.array(x, dt, copy=True)
                                       for x, dt in zip(xs, dtypes)],
                   static_argnums=1)


MOE = {"bias_ignored": moe_bias_ignored,
       "not_renormalised": moe_not_renormalised,
       "capacity_drops": moe_capacity_drops,
       "wrong_held": moe_wrong_held,
       "control_fp8": moe_control_fp8}
