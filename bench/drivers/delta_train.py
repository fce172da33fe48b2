"""Driver for delta-synced training deployments: pods of one local-SGD
job on one chip, each training K local steps per outer round with the
program's jitted step, gossiping each round's pseudo-gradient over the
lossy simulated network (``DeltaSyncPod.do_round``), then gossiping to
convergence. Jobs run back to back from the same seeded init.

Set-up makes the weights on the device from the seed, builds the step
once, and runs the first job through the window's own calls: it
compiles every program and, on the first pod's first three local
steps, reads the losses, the first gradient as the optimizer holds it
(its first moment over 1 - b1) and each parameter's change after the
three steps. After the window those readings are compared with the
float32 reference in ``bench/reference/qwen_ref.py`` on the same
weights and rows, and the last job's outer parameters with
``init + sum(updates) / P``.
"""

from __future__ import annotations

import gc
import random
import time
from types import SimpleNamespace
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from .. import work, workgen
from ..reference import qwen_ref
from ..seeds import jax_key

N_REFERENCE_STEPS = 3


def model_config(cfg: dict):
    """The program's model configuration for a published config."""
    from repro.models import ModelConfig
    return ModelConfig(
        name=cfg["name"], family="dense",
        n_layers=int(cfg["num_hidden_layers"]),
        d_model=int(cfg["hidden_size"]),
        n_heads=int(cfg["num_attention_heads"]),
        n_kv_heads=int(cfg["num_key_value_heads"]),
        d_ff=int(cfg["intermediate_size"]), vocab=int(cfg["vocab_size"]),
        qkv_bias=bool(cfg["attention_bias_qkv"]),
        rope_theta=float(cfg["rope_theta"]),
        tie_embeddings=bool(cfg["tie_word_embeddings"]),
        act="swiglu", norm="rms", pos="rope", subquadratic=False,
        dtype=cfg["train"]["param_dtype"])


def leaf_name(path) -> str:
    """The reference's name of a program parameter: dict keys joined by
    dots, with the stacked layer group spelled ``layers``."""
    parts = []
    for k in path:
        key = getattr(k, "key", None)
        if key == "groups":
            parts.append("layers")
        elif key is not None:
            parts.append(str(key))
    return ".".join(parts)


def make_params(mcfg, seed: int):
    """Weights from the seed, made on the device in one jitted call in
    the program's parameter layout and dtypes: embedding and biases
    N(0, 0.02), each projection N(0, 1/fan_in), norm scales 1. Returns
    ``(program tree, {reference name: array})`` over the same arrays."""
    from repro.models import init_model
    shapes = jax.eval_shape(lambda: init_model(mcfg, jax.random.PRNGKey(0))[0])
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    names = [leaf_name(p) for p, _ in flat]

    def gen(key):
        out = []
        for i, (name, sd) in enumerate(zip(names, (s for _, s in flat))):
            k = jax.random.fold_in(key, i)
            last = name.rsplit(".", 1)[-1]
            if last == "scale":
                x = jnp.ones(sd.shape, jnp.float32)
            elif name == "embed.tok" or last.startswith("b"):
                x = 0.02 * jax.random.normal(k, sd.shape, jnp.float32)
            else:
                x = jax.random.normal(k, sd.shape, jnp.float32) \
                    / np.sqrt(sd.shape[-2])
            out.append(x.astype(sd.dtype))
        return out

    leaves = jax.jit(gen)(jax_key(seed, 1))
    return treedef.unflatten(leaves), dict(zip(names, leaves))


@jax.jit
def _leaf_norms(tree):
    return [jnp.linalg.norm(x.astype(jnp.float32).ravel())
            for x in jax.tree_util.tree_leaves(tree)]


@jax.jit
def _change_norms(master, init):
    return [jnp.linalg.norm((m - i.astype(jnp.float32)).ravel())
            for m, i in zip(jax.tree_util.tree_leaves(master),
                            jax.tree_util.tree_leaves(init))]


@jax.jit
def _outer_leaf(x0, ups, outs):
    """Worst |outer - reference| over its rounding bound for one leaf, and
    the number of pods whose outer params differ from the first's."""
    n, pods = len(ups), len(outs)
    ref = x0.astype(jnp.float32) + sum(u.astype(jnp.float32)
                                       for u in ups) / pods
    out = outs[0].astype(jnp.float32)
    eps = float(jnp.finfo(outs[0].dtype).eps)
    tol = (1 + eps) ** n * (eps * jnp.maximum(jnp.abs(out), jnp.abs(ref))
                            + eps / 2 * (n - 1)
                            * sum(jnp.abs(u.astype(jnp.float32))
                                  for u in ups) / pods)
    ratio = jnp.max(jnp.abs(out - ref) / jnp.maximum(tol, 1e-30))
    apart = sum(jnp.any(o != outs[0]).astype(jnp.int32) for o in outs[1:])
    return ratio, apart


def outer_check(pods) -> tuple:
    """``(worst error over tolerance, pods apart)`` of every pod's outer
    params against init + sum(updates) / P from the pods' own
    contributions."""
    init = jax.tree_util.tree_leaves(pods[0].outer.init)
    own = [jax.tree_util.tree_leaves(upd) for p in pods
           for (producer, _), upd in p.X.dots if producer == p.id]
    outer = [jax.tree_util.tree_leaves(p.params()) for p in pods]
    worst, apart = 0.0, 0
    for li, x0 in enumerate(init):
        r, a = _outer_leaf(x0, [u[li] for u in own], [o[li] for o in outer])
        worst, apart = max(worst, float(r)), max(apart, int(a))
    return worst, apart


class Trainer:
    """One cell's jobs: the step, the data feed and the readings."""

    def __init__(self, cell, seed: int, rec):
        from repro.launch.train import make_delta_step
        cfg, tr = cell.config, cell.traffic
        self.rec, self.seed = rec, seed
        self.train = cfg["train"]
        self.pods = int(self.train["pods"])
        self.rounds = int(self.train["outer_rounds"])
        self.k = int(tr["local_steps"])
        self.batch, self.seq = int(tr["batch"]), int(tr["seq"])
        self.vocab = int(cfg["vocab_size"])
        self.mcfg = model_config(cfg)
        self.init, self.named = make_params(self.mcfg, seed)
        self.names = list(self.named)
        opt = self.train["optimizer"]
        self.b1 = float(opt["b1"])
        self.step = make_delta_step(self.mcfg, SimpleNamespace(
            lr=float(opt["lr"]), steps=self.rounds * self.k))
        self.done: List[tuple] = []          # (finish time, loss) per step
        self.readings: Dict[str, object] = {"loss": []}
        self.unconverged = 0                 # jobs whose pods never agreed

    def batch_of(self, job: int, rank: int, step: int) -> dict:
        return workgen.token_batch(self.seed, job, rank, step, self.batch,
                                   self.seq, self.vocab)

    def _read(self, k: int, loss: float, opt) -> None:
        """The first pod's first steps of the first job: what the
        reference is compared with."""
        if k < N_REFERENCE_STEPS:
            self.readings["loss"].append(loss)
        if k == 0:
            self.readings["grad_norm"] = dict(zip(self.names, (
                float(x) / (1 - self.b1) for x in _leaf_norms(opt["m"]))))
        if k == N_REFERENCE_STEPS - 1:
            self.readings["change_norm"] = dict(zip(self.names, (
                float(x) for x in _change_norms(opt["master"], self.init))))

    def local_update_for(self, job: int):
        from repro.optim.adamw import init_opt_state

        def local_update(params, round_idx, pod_id):
            # K local steps on this pod's rows, from a fresh optimizer
            # state each round; the step donates, so it starts from a copy
            rank = int(pod_id.split("pod")[-1])
            opt = init_opt_state(params)
            p = jax.tree_util.tree_map(jnp.copy, params)
            for k in range(self.k):
                b = self.batch_of(job, rank, round_idx * self.k + k)
                batch = {n: jnp.asarray(v) for n, v in b.items()}
                with self.rec.span("local_step"):
                    p, opt, m = self.step(p, opt, batch)
                    loss = float(m["loss"])
                self.done.append((time.perf_counter(), loss))
                if job == 0 and rank == 0 and round_idx == 0:
                    self._read(k, loss, opt)
            return p
        return local_update

    def job(self, j: int):
        """One job: every pod's outer rounds with gossip between them,
        then gossip to convergence. Returns the pods."""
        from repro.core import (NetConfig, Simulator, make_policy,
                                run_to_convergence)
        from repro.sync import DeltaSyncPod
        net = self.train["net"]
        sim = Simulator(NetConfig(loss=float(net["loss"]),
                                  dup=float(net["dup"]),
                                  seed=self.seed * 1_000_003 + j))
        ids = [f"pod{k}" for k in range(self.pods)]
        update = self.local_update_for(j)
        pods = [sim.add_node(DeltaSyncPod(
            i, [o for o in ids if o != i], self.init, update,
            num_pods=self.pods, rng=random.Random(self.seed + n),
            policy=make_policy("all"))) for n, i in enumerate(ids)]
        for _ in range(self.rounds):
            for p in pods:
                with self.rec.span("round"):
                    p.do_round()
            with self.rec.span("gossip"):
                sim.run_for(float(self.train["gossip_between_rounds_s"]))
        with self.rec.span("converge"):
            try:
                run_to_convergence(sim, pods, interval=1.0, max_time=50_000)
            except AssertionError:
                self.unconverged += 1
        return pods


def run(cell, seed: int, seconds: float, rec, devices) -> dict:
    tr = Trainer(cell, seed, rec)
    tr.job(0)                               # set-up: compiles, reads
    gc.collect()
    t0 = rec.begin_window()
    end = t0 + seconds
    j = 1
    while time.perf_counter() < end:
        # a job's pods and simulator refer to each other: only a
        # collection frees the last job's dots before the next starts
        pods = None
        with rec.span("job_end"):
            gc.collect()
        pods = tr.job(j)
        j += 1
    rec.end_window()
    rec.reduce_trace()

    t1 = rec.window[1]
    in_window = [loss for t, loss in tr.done if t0 < t <= t1]
    tokens = len(in_window) * tr.batch * tr.seq
    rec.counts["tokens_in_window"] = tokens
    rec.counts["model_flops_in_window"] = tokens * \
        work.dense_lm_train_flops_per_token(cell.config, tr.seq)
    rec.attempted = len(in_window)
    rec.failed = int(sum(not np.isfinite(x) for x in in_window))
    rec.log(f"train: {j - 1} jobs in the window, {len(in_window)} local "
            f"steps, losses {min(in_window, default=None)}.."
            f"{max(in_window, default=None)}")

    worst, apart = outer_check(pods)
    del pods
    rec.read_memory_peak(devices)
    step_losses = [x for _, x in tr.done]
    readings = tr.readings
    del tr.step, tr.done
    gc.collect()
    check_training(cell, tr, readings, rec)
    rec.check("outer_error_over_tolerance", worst, 1.0)
    rec.check("pods_apart", apart, 0)
    rec.check("jobs_unconverged", tr.unconverged, 0)
    rec.check("nonfinite_losses",
              int(sum(not np.isfinite(x) for x in step_losses)), 0)
    return {}


def reference_readings(cell, tr) -> dict:
    """The reference's readings on the rows of the first pod's first
    steps of the first job, from the same weights."""
    batches = [tr.batch_of(0, 0, k) for k in range(N_REFERENCE_STEPS)]
    return qwen_ref.train_readings(
        cell.config, cell.config["train"]["optimizer"], tr.named, batches,
        total_steps=tr.rounds * tr.k,
        micro_batches=int(cell.traffic["reference_micro_batches"]))


def gaps(prog: dict, ref: dict) -> dict:
    """The compared numbers: the first step's loss gap and the largest
    over the steps, and, by the worst parameter, the gap between the
    program's norm and the reference's over the larger of the
    reference's norm of that parameter and the median parameter's (first
    gradient; change after the steps, counting only parameters whose
    reference gradient is at least a thousandth of the median's)."""
    loss_gap = max(abs(a - b) for a, b in zip(prog["loss"], ref["loss"]))
    raw = ref["raw_grad_norm"]
    floor = 1e-3 * float(np.median(list(raw.values())))
    moving = [k for k in raw if raw[k] >= floor]

    def worst(key, names):
        med = float(np.median([ref[key][k] for k in names]))
        return max(abs(prog[key][k] - ref[key][k]) / max(ref[key][k], med)
                   for k in names)

    return {"first_loss_gap": abs(prog["loss"][0] - ref["loss"][0]),
            "loss_gap": loss_gap,
            "grad_norm_gap": worst("grad_norm", list(raw)),
            "change_norm_gap": worst("change_norm", moving)}


def check_training(cell, tr, readings, rec) -> None:
    ref = reference_readings(cell, tr)
    g = gaps(readings, ref)
    rec.info["gaps"] = g
    limits = cell.config["limits"]
    for name in ("first_loss_gap", "loss_gap", "grad_norm_gap",
                 "change_norm_gap"):
        rec.check(name, g[name], float(limits[name]))
