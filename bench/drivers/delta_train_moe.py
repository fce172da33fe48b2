"""Driver for delta-synced training of a mixture-of-experts model with
latent attention (DeepSeek-V3 layout) on one chip's share of an
expert-parallel deployment: the configuration's leading dense layers and
MoE layers, the experts this chip holds (``held_experts``) behind a
router over all of them (``router_experts``), and its slice of the
vocabulary.

The job, the pods, the lossy gossip and the local-update loop are the
dense driver's (``delta_train``); this module supplies the model
configuration, the weights, the reference readings, the work count and
the run. Of the dense cell's gaps it holds the run to ``change_norm_gap``
alone, and adds the routing of the first step: ``load_gap``, for the
worst MoE layer, the L1 distance between the program's and the
reference's pairs per expert over tokens x top-k. The loss and
first-gradient gaps are reported under ``gaps`` and not checked: at this
size the float8 control reads them under twice the largest sound reading,
so no limit between the two has room on both sides.
"""

from __future__ import annotations

import functools
import gc
import time
from types import SimpleNamespace
from typing import List

import jax
import jax.numpy as jnp
import numpy as np

from .. import work_moe
from ..reference import kanana_ref
from ..seeds import jax_key
from . import delta_train as dt

BIAS_STD = 0.02              # the selection bias, N(0, BIAS_STD) in f32
CHECKED = ("change_norm_gap", "load_gap")


def model_config(cfg: dict):
    """The program's model configuration for a configuration file."""
    from repro.models import LayerSpec, MLASpec, ModelConfig, MoESpec
    if cfg["q_lora_rank"] is not None or cfg["rope_scaling"] is not None:
        raise ValueError("only MLA without a query latent and without "
                         "rope scaling is described here")
    if cfg["n_group"] != 1 or cfg["topk_group"] != 1:
        raise ValueError("group-limited routing is not modelled")
    layers, dense = int(cfg["num_hidden_layers"]), \
        int(cfg["first_k_dense_replace"])
    first, held = (int(x) for x in cfg["held_experts"])
    assert held == int(cfg["n_routed_experts"])
    ff = int(cfg["moe_intermediate_size"])
    return ModelConfig(
        name=cfg["name"], family="moe", n_layers=layers,
        d_model=int(cfg["hidden_size"]),
        n_heads=int(cfg["num_attention_heads"]),
        n_kv_heads=int(cfg["num_key_value_heads"]),
        d_ff=int(cfg["intermediate_size"]), vocab=int(cfg["vocab_size"]),
        layout=tuple(LayerSpec(kind="mla",
                               mlp="dense" if i < dense else "moe")
                     for i in range(layers)),
        mla=MLASpec(q_lora_rank=None,
                    kv_lora_rank=int(cfg["kv_lora_rank"]),
                    qk_nope_head_dim=int(cfg["qk_nope_head_dim"]),
                    qk_rope_head_dim=int(cfg["qk_rope_head_dim"]),
                    v_head_dim=int(cfg["v_head_dim"])),
        moe=MoESpec(num_experts=int(cfg["router_experts"]),
                    top_k=int(cfg["num_experts_per_tok"]), expert_d_ff=ff,
                    num_shared_experts=int(cfg["n_shared_experts"]),
                    shared_d_ff=ff, score_func=cfg["scoring_func"],
                    norm_topk=bool(cfg["norm_topk_prob"]),
                    routed_scale=float(cfg["routed_scaling_factor"]),
                    select_bias=cfg["topk_method"] == "noaux_tc",
                    router_f32=True,
                    held=(first, held)),
        rope_theta=float(cfg["rope_theta"]),
        tie_embeddings=bool(cfg["tie_word_embeddings"]),
        act="swiglu", norm="rms", pos="rope", subquadratic=False,
        attn_impl="chunked", attn_block=kanana_ref.QUERY_BLOCK,
        dtype=cfg["train"]["param_dtype"])


def param_names(mcfg) -> List[str]:
    """The reference's name of each program parameter, in leaf order:
    ``delta_train.leaf_name`` with each stacked layer group named by the
    kind of its MLP (``dense.`` or ``moe.``)."""
    from repro.models import init_model, layout_groups
    kinds = [block[0].mlp for block, _ in
             layout_groups(mcfg.default_layout())]
    shapes = jax.eval_shape(lambda: init_model(mcfg, jax.random.PRNGKey(0))[0])
    names = []
    for path, _ in jax.tree_util.tree_flatten_with_path(shapes)[0]:
        name = dt.leaf_name(path)
        if name.startswith("layers."):
            name = kinds[path[1].idx] + name[len("layers"):]
        names.append(name)
    return names


def make_params(mcfg, seed: int):
    """Weights from the seed, made on the device in one jitted call in
    the program's layout and dtypes, sliced from one draw of standard
    normals: embedding N(0, 0.02), the selection bias N(0, ``BIAS_STD``),
    each projection and the router N(0, 1/fan_in), norm scales 1.
    Returns ``(program tree, {reference name: array})`` over the same
    arrays."""
    gen, treedef, names = _generator(mcfg)
    arrays = gen(jax_key(seed, 1))
    return treedef.unflatten(arrays), dict(zip(names, arrays))


@functools.lru_cache(maxsize=4)
def _generator(mcfg):
    """``make_params``' jitted draw for one model, its tree and its
    names, kept for the process: runs of one cell share the compile."""
    from repro.models import init_model
    shapes = jax.eval_shape(lambda: init_model(mcfg, jax.random.PRNGKey(0))[0])
    leaves, treedef = jax.tree_util.tree_flatten(shapes)
    names = param_names(mcfg)
    sizes = [int(np.prod(sd.shape)) for sd in leaves]
    starts = np.cumsum([0] + sizes)

    def gen(key):
        z = jax.random.normal(key, (int(starts[-1]),), jnp.float32)
        out = []
        for name, sd, a, b in zip(names, leaves, starts[:-1], starts[1:]):
            x = z[a:b].reshape(sd.shape)
            if name.endswith(".scale"):
                x = jnp.ones(sd.shape, jnp.float32)
            elif name == "embed.tok":
                x = 0.02 * x
            elif name.endswith(".bias"):
                x = BIAS_STD * x
            else:
                x = x / np.sqrt(sd.shape[-2])
            out.append(x.astype(sd.dtype))
        return out

    return jax.jit(gen), treedef, names


class Trainer(dt.Trainer):
    """The dense driver's jobs on this model. The step also keeps, on the
    device and unread until the window closes, each step's rows per held
    expert (``moe_rows``), and the first step's pairs per expert."""

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
        step = make_delta_step(self.mcfg, SimpleNamespace(
            lr=float(opt["lr"]), steps=self.rounds * self.k))
        self.rows: List = []                 # per step, aligned with done
        self.first_load = None

        def counted(p, opt, batch):
            p, opt, m = step(p, opt, batch)
            if self.first_load is None:
                self.first_load = m.get("moe_load")
            self.rows.append(m.get("moe_rows"))
            return p, opt, m

        self.step = counted
        self.done = []
        self.readings = {"loss": []}
        self.unconverged = 0


def run(cell, seed: int, seconds: float, rec, devices) -> dict:
    tr = Trainer(cell, seed, rec)
    tr.job(0)                               # set-up: compiles, reads
    gc.collect()
    t0 = rec.begin_window()
    end = t0 + seconds
    j = 1
    while time.perf_counter() < end:
        pods = None
        with rec.span("job_end"):
            gc.collect()
        pods = tr.job(j)
        j += 1
    rec.end_window()
    rec.reduce_trace()

    t1 = rec.window[1]
    steps = [i for i, (t, _) in enumerate(tr.done) if t0 < t <= t1]
    in_window = [tr.done[i][1] for i in steps]
    tokens = len(in_window) * tr.batch * tr.seq
    rec.counts["tokens_in_window"] = tokens
    rec.counts["model_flops_in_window"] = tokens * \
        work_moe.train_flops_per_token(cell.config, tr.seq)
    counted = [tr.rows[i] for i in steps if tr.rows[i] is not None]
    if counted:
        rows = np.asarray(jax.device_get(jnp.stack(counted)))
        rec.info["moe_rows"] = rows          # [steps, MoE layers, held]
        rec.counts["gmm_flops_in_window"] = float(rows.sum()) * \
            work_moe.gmm_flops_per_row(cell.config)
    rec.attempted = len(in_window)
    rec.failed = int(sum(not np.isfinite(x) for x in in_window))
    rec.log(f"train: {j - 1} jobs in the window, {len(in_window)} local "
            f"steps, losses {min(in_window, default=None)}.."
            f"{max(in_window, default=None)}")

    worst, apart = dt.outer_check(pods)
    del pods
    rec.read_memory_peak(devices)
    step_losses = [x for _, x in tr.done]
    readings = dict(tr.readings)
    readings["load"] = (None if tr.first_load is None
                        else np.asarray(jax.device_get(tr.first_load)))
    del tr.step, tr.done, tr.rows
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
    batches = [tr.batch_of(0, 0, k) for k in range(dt.N_REFERENCE_STEPS)]
    return kanana_ref.train_readings(
        cell.config, cell.config["train"]["optimizer"], tr.named, batches,
        total_steps=tr.rounds * tr.k,
        micro_batches=int(cell.traffic["reference_micro_batches"]))


def load_gap(prog_load, ref_load, tokens: int, top_k: int) -> float:
    """For the worst MoE layer, ``sum |program pairs per expert -
    reference pairs per expert|`` over tokens x top-k; 1 where the
    program reported no loads."""
    if prog_load is None:
        return 1.0
    diff = np.abs(np.asarray(prog_load, np.int64)
                  - np.asarray(ref_load, np.int64)).sum(axis=-1)
    return float(diff.max()) / (tokens * top_k)


def check_training(cell, tr, readings, rec) -> None:
    ref = reference_readings(cell, tr)
    g = dt.gaps(readings, ref)
    g["load_gap"] = load_gap(readings["load"], ref["load"],
                             tr.batch * tr.seq,
                             int(cell.config["num_experts_per_tok"]))
    rec.info["gaps"] = g
    rec.info["bias_moved"] = ref["bias_moved"]
    rec.log("train: gaps " + ", ".join(f"{k} {v:.6g}" for k, v in g.items()))
    rec.log(f"train: the bias moved the choice of {ref['bias_moved']} of "
            f"the first step's tokens, per MoE layer")
    limits = cell.config["limits"]
    for name in CHECKED:
        rec.check(name, g[name], float(limits[name]))
