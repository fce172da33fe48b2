"""Faults planted under a cell's timed path, to show that its comparison
fails when the path is wrong, and each cell's control (the reference in
the program's place at the next lower precision, or a broken guarantee).
Each takes the cell and returns a context manager that patches the
program for the duration of one run; the benchmark's own runs plant
none. ``bench/tools/control.py`` runs them on the chip and
``bench/tests`` at test sizes.
"""

from __future__ import annotations

import contextlib

import numpy as np


@contextlib.contextmanager
def _patched(obj, name: str, value):
    old = getattr(obj, name)
    setattr(obj, name, value)
    try:
        yield
    finally:
        setattr(obj, name, old)


# -- keyed store --------------------------------------------------------------

def store_join_unchanged(cell):
    """Every scatter join returns the resident columns as they were."""
    from repro.kernels import ops
    return _patched(ops, "scatter_join",
                    lambda vals, vers, maxabs, sumsq, *a, **k:
                    (vals, vers, maxabs, sumsq))


def store_join_half_rows(cell):
    """Every scatter join applies only the first half of its rows."""
    from repro.kernels import resident
    inner = resident._scatter_ingest

    def half(ra, a_store, b_store, life, plan):
        h = int(plan[0].shape[0]) // 2
        return inner(ra, a_store, b_store, life,
                     tuple(x[:h] for x in plan))
    return _patched(resident, "_scatter_ingest", half)


def store_exchange_dropped(cell):
    """The network between the replicas carries nothing."""
    from repro.core.sim import Simulator
    return _patched(Simulator, "send", lambda self, src, dst, msg: None)


def store_read_altered(cell):
    """``StoreReplica.get`` answers with every value off by one."""
    from repro.core.propagation import StoreReplica
    from repro.core.tensor_lattice import ChunkedTensor, TensorState
    inner = StoreReplica.get

    def get(self, key, typ=None):
        ts = inner(self, key, typ)
        return TensorState(tuple(
            (n, ChunkedTensor(np.asarray(ct.values) + 1.0, ct.versions))
            for n, ct in ts.chunks), ts.lamport)
    return _patched(StoreReplica, "get", get)


def store_ack_without_join(cell):
    """The control: a replica acknowledges every delta it receives and
    keeps none, breaking the guarantee that an acknowledged update is
    held by every replica."""
    from repro.core.propagation import Replica
    return _patched(Replica, "_receive_delta",
                    lambda self, src, d, n, ghost=None:
                    self._post(src, ("ack", n)))


# -- delta-synced training ----------------------------------------------------

def _wrap_step(make):
    """Patch the program's step factory: ``make(step, args)`` returns
    the step the run uses in place of ``step``."""
    from repro.launch import train
    inner = train.make_delta_step

    def make_delta_step(cfg, args):
        return make(inner(cfg, args), args)
    return _patched(train, "make_delta_step", make_delta_step)


def train_step_unchanged(cell):
    """The step computes its loss but returns params and optimizer state
    as they came in."""
    import jax
    import jax.numpy as jnp

    def make(step, args):
        def same(p, opt, batch):
            copy = jax.tree_util.tree_map(jnp.copy, (p, opt))
            _, _, m = step(*copy, batch)
            return p, opt, m
        return same
    return _wrap_step(make)


def train_half_batch(cell):
    """The step sees only the first half of its batch's rows, and its
    loss is the mean over those."""
    def make(step, args):
        def half(p, opt, batch):
            n = next(iter(batch.values())).shape[0] // 2
            return step(p, opt, {k: v[:n] for k, v in batch.items()})
        return half
    return _wrap_step(make)


def train_exchange_dropped(cell):
    """The network between the pods carries nothing."""
    return store_exchange_dropped(cell)


def train_outer_altered(cell):
    """Every pod's outer parameters come out one percent too large."""
    import jax
    from repro.sync import DeltaSyncPod
    inner = DeltaSyncPod.params
    return _patched(DeltaSyncPod, "params", lambda self: jax.tree_util.tree_map(
        lambda x: x * 1.01, inner(self)))


def train_control_fp8(cell):
    """The control: the reference model and its AdamW, computed with every
    contraction in float8 (``qwen_ref.round_fp8``; the configuration
    trains in bfloat16), in the program step's place. It keeps the
    program's parameter and optimizer-state trees and donates its
    parameters as the program's step does, so the run around it is the
    timed path's."""
    import jax
    import jax.numpy as jnp
    from .drivers.delta_train import leaf_name
    from .reference import qwen_ref

    def make(step, args):
        ref = qwen_ref.AdamW(
            cell.config, cell.config["train"]["optimizer"], args.steps,
            rounding=qwen_ref.round_fp8,
            micro_batches=int(cell.traffic["reference_micro_batches"]))

        def fp8_step(p, opt, batch):
            flat, treedef = jax.tree_util.tree_flatten_with_path(
                opt["master"])
            names = [leaf_name(path) for path, _ in flat]

            def named(tree):
                return dict(zip(names, jax.tree_util.tree_leaves(tree)))

            def tree(d):
                return treedef.unflatten([d[n] for n in names])

            dtypes = [x.dtype for x in jax.tree_util.tree_leaves(p)]
            for x in jax.tree_util.tree_leaves(p):
                x.delete()
            t = int(opt["step"]) + 1
            master = named(opt["master"])
            loss, g = ref.grad(master, batch["tokens"], batch["labels"])
            master, m, v, _ = ref.update(master, g, named(opt["m"]),
                                         named(opt["v"]), t)
            # buffers of their own: the next step deletes these
            new_p = treedef.unflatten([jnp.array(master[n], dt, copy=True)
                                       for n, dt in zip(names, dtypes)])
            return new_p, {"m": tree(m), "v": tree(v), "master": tree(master),
                           "step": opt["step"] + 1}, {"loss": loss}
        return fp8_step
    return _wrap_step(make)


STORE = {"join_unchanged": store_join_unchanged,
         "join_half_rows": store_join_half_rows,
         "exchange_dropped": store_exchange_dropped,
         "read_altered": store_read_altered,
         "control_ack_without_join": store_ack_without_join}
TRAIN = {"step_unchanged": train_step_unchanged,
         "half_batch": train_half_batch,
         "exchange_dropped": train_exchange_dropped,
         "outer_altered": train_outer_altered,
         "control_fp8": train_control_fp8}
# by the ``driver`` a configuration names
BY_DRIVER = {"store": STORE, "delta_train": TRAIN}
