"""kanana-2-30b-a3b's layers against the plain float32 reference
(``bench/reference/kanana_ref.py``) at its REDUCED size on the CPU: MLA
without a query latent, the held-experts layer (its shares add up to the
whole layer, and it drops no token), the training step and three AdamW
steps; the megablox path of the grouped matmul against ``ragged_dot``;
and DeepSeek-V2's routing without renormalisation."""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.drivers import delta_train_moe as drv
from bench.reference import kanana_ref
from repro.configs import get_config
from repro.models import moe
from repro.models.mla import init_mla, mla_full
from repro.models.moe import apply_moe, init_moe
from repro.optim import AdamWConfig
from repro.optim.adamw import init_opt_state
from repro.runtime import TrainConfig, make_train_step

CFG = get_config("kanana-2-30b-a3b", reduced=True)
moe_layer = jax.jit(apply_moe, static_argnums=1)


def config_file(cfg) -> dict:
    """The REDUCED model as a configuration file describes it."""
    m, e = cfg.mla, cfg.moe
    return {"name": cfg.name, "num_hidden_layers": cfg.n_layers,
            "first_k_dense_replace": 1, "hidden_size": cfg.d_model,
            "num_attention_heads": cfg.n_heads,
            "num_key_value_heads": cfg.n_kv_heads,
            "intermediate_size": cfg.d_ff, "vocab_size": cfg.vocab,
            "q_lora_rank": None, "kv_lora_rank": m.kv_lora_rank,
            "qk_nope_head_dim": m.qk_nope_head_dim,
            "qk_rope_head_dim": m.qk_rope_head_dim,
            "v_head_dim": m.v_head_dim, "rope_scaling": None,
            "rope_theta": cfg.rope_theta, "rms_norm_eps": 1e-6,
            "moe_intermediate_size": e.expert_d_ff,
            "router_experts": e.num_experts,
            "n_routed_experts": e.held[1], "held_experts": list(e.held),
            "n_shared_experts": e.num_shared_experts,
            "num_experts_per_tok": e.top_k, "scoring_func": "sigmoid",
            "topk_method": "noaux_tc", "n_group": 1, "topk_group": 1,
            "norm_topk_prob": e.norm_topk,
            "routed_scaling_factor": e.routed_scale,
            "tie_word_embeddings": False,
            "train": {"param_dtype": "float32"}}


def _normal(seed, shape, scale=1.0):
    rng = np.random.default_rng(seed)
    return jnp.asarray(scale * rng.standard_normal(shape), jnp.float32)


@functools.lru_cache(maxsize=None)
def _init(fn, cfg):
    """``fn``'s float32 parameters of ``cfg`` from a key, in one jitted
    call."""
    return jax.jit(lambda key: fn(cfg, key, jnp.float32)[0])


def _moe_params(cfg, seed):
    p = _init(init_moe, cfg)(jax.random.PRNGKey(seed))
    p["bias"] = _normal(seed + 1, p["bias"].shape, 0.02)
    return p


def _held(cfg, first, n):
    return dataclasses.replace(cfg, moe=dataclasses.replace(
        cfg.moe, held=(first, n)))


@functools.lru_cache(maxsize=None)
def _whole_layer():
    """The uncut layer's parameters, an input, its shared experts' output,
    and the reference's output and pairs per expert."""
    full = _moe_params(CFG, 0)
    h = _normal(9, (2, 16, CFG.d_model))
    shared = jax.jit(moe._shared_experts, static_argnums=1)(
        full, CFG, h.reshape(-1, CFG.d_model)).reshape(h.shape)
    model = kanana_ref.Model(config_file(_held(CFG, 0, CFG.moe.num_experts)))
    named = {"mlp." + k: v for k, v in full.items() if k != "shared"}
    named.update({"mlp.shared." + k: v for k, v in full["shared"].items()})
    ref, load, _ = jax.jit(model.moe_mlp)(h, named)
    return full, h, shared, ref, load


@pytest.mark.parametrize("split", [(8,), (4, 4), (3, 5), (1, 2, 5)],
                         ids=str)
def test_held_shares_add_up_to_the_whole_layer(split):
    """Every split of the experts into held ranges: the routed parts the
    shares give, with the shared experts counted once, are the uncut
    reference layer."""
    full, h, shared, ref, load = _whole_layer()
    total, first = 0.0, 0
    for n in split:
        part = {k: (v[first:first + n] if k in ("wi", "wg", "wo") else v)
                for k, v in full.items()}
        y, aux, stats = moe_layer(part, _held(CFG, first, n), h)
        total = total + y
        assert stats["moe_rows"].shape == (n,)
        first += n
    total = total - (len(split) - 1) * shared
    np.testing.assert_allclose(np.asarray(total), np.asarray(ref),
                               rtol=2e-4, atol=2e-5)
    np.testing.assert_array_equal(np.asarray(stats["moe_load"]),
                                  np.asarray(load))
    assert float(aux) == 0.0          # a bias router adds no Switch loss


def test_a_held_expert_that_every_token_chooses_drops_none():
    """A bias that sends every token to one held expert: the layer
    computes all of them (a capacity of 1.25x the mean would keep under
    a third)."""
    cfg = _held(CFG, 2, 4)
    full = _moe_params(CFG, 3)
    full["bias"] = full["bias"].at[3].set(100.0)
    part = {k: (v[2:6] if k in ("wi", "wg", "wo") else v)
            for k, v in full.items()}
    h = _normal(4, (2, 16, CFG.d_model))
    y, _, stats = moe_layer(part, cfg, h)
    assert int(stats["moe_rows"][1]) == 32 == int(stats["moe_load"][3])
    ref_cfg = config_file(cfg)
    named = {"mlp." + k: v for k, v in part.items() if k != "shared"}
    named.update({"mlp.shared." + k: v for k, v in part["shared"].items()})
    ref, _, _ = jax.jit(kanana_ref.Model(ref_cfg).moe_mlp)(h, named)
    np.testing.assert_allclose(np.asarray(y), np.asarray(ref), rtol=2e-4,
                               atol=2e-5)


def test_mla_without_a_query_latent_matches_the_reference():
    p = _init(init_mla, CFG)(jax.random.PRNGKey(5))
    assert "w_q" in p and "w_dq" not in p and "q_norm" not in p
    x = _normal(6, (2, 16, CFG.d_model))
    pos = jnp.asarray(np.broadcast_to(np.arange(16)[None], (2, 16)))
    named = {"mix." + k: v for k, v in p.items() if k != "kv_norm"}
    named["mix.kv_norm.scale"] = p["kv_norm"]["scale"]
    named["norm1.scale"] = jnp.ones((CFG.d_model,))
    model = kanana_ref.Model(config_file(CFG))
    # the reference's block norms its input and adds it to the output;
    # the program's attention is given the normed input (two query
    # blocks of CFG.attn_block rows)
    h = jax.jit(model._rms)(x, 1.0)
    y, _ = jax.jit(mla_full, static_argnums=(1, 2))(p, CFG, None, h, pos)
    ref = jax.jit(model._attention)(h, named) - h
    np.testing.assert_allclose(np.asarray(y), np.asarray(ref), rtol=2e-4,
                               atol=2e-5)


def test_training_steps_match_the_reference():
    """Loss, the first gradient as AdamW takes it and each parameter's
    change over three steps, program against reference, in float32."""
    file = config_file(CFG)
    mcfg = drv.model_config(file)
    params, named = drv.make_params(mcfg, 17)
    opt = {"lr": 1e-2, "b1": 0.9, "b2": 0.95, "eps": 1e-8,
           "weight_decay": 0.1, "clip_norm": 1.0, "warmup_steps": 1,
           "min_lr_frac": 0.1}
    rng = np.random.default_rng(3)
    batches = []
    for _ in range(3):
        toks = rng.integers(0, CFG.vocab, (2, 17)).astype(np.int32)
        batches.append({"tokens": toks[:, :-1], "labels": toks[:, 1:]})
    ref = kanana_ref.train_readings(file, opt, named, batches,
                                    total_steps=3)
    step = jax.jit(make_train_step(mcfg, TrainConfig(optimizer=AdamWConfig(
        lr=opt["lr"], warmup_steps=1, total_steps=3))))
    norms = jax.jit(lambda t: [jnp.linalg.norm(x)
                               for x in jax.tree_util.tree_leaves(t)])
    diff = jax.jit(functools.partial(jax.tree_util.tree_map, jnp.subtract))
    state = jax.jit(init_opt_state)(params)
    p, losses = params, []
    for t, b in enumerate(batches):
        p, state, m = step(p, state, {k: jnp.asarray(v) for k, v in b.items()})
        losses.append(float(m["loss"]))
        if t == 0:
            grad = [float(x) / (1 - opt["b1"]) for x in norms(state["m"])]
            assert m["moe_load"].shape == (1, CFG.moe.num_experts)
            np.testing.assert_array_equal(np.asarray(m["moe_load"]),
                                          ref["load"])
    change = [float(x) for x in norms(diff(state["master"], params))]
    names = list(named)
    np.testing.assert_allclose(losses, ref["loss"], rtol=1e-5)
    np.testing.assert_allclose(grad, [ref["grad_norm"][n] for n in names],
                               rtol=1e-3, atol=1e-7)
    np.testing.assert_allclose(change, [ref["change_norm"][n]
                                        for n in names], rtol=1e-3,
                               atol=1e-7)
    assert ref["grad_norm"]["moe.mlp.bias"] == 0.0   # no gradient


def test_megablox_path_matches_ragged_dot(monkeypatch):
    """The TPU path of the grouped matmul (megablox ``gmm``, run here in
    interpret mode) against ``ragged_dot``: rows past the held groups are
    zero, values and gradients agree."""
    from jax.experimental.pallas.ops.tpu import megablox
    rng = np.random.default_rng(0)
    lhs = jnp.asarray(rng.standard_normal((200, 128)), jnp.float32)
    rhs = jnp.asarray(rng.standard_normal((3, 128, 128)), jnp.float32)
    rows = jnp.array([50, 0, 70], jnp.int32)

    def f(lhs, rhs):
        """The product and the gradient of the sum of its squares."""
        out, vjp = jax.vjp(lambda *a: moe._grouped(*a, rows), lhs, rhs)
        return out, vjp(2 * out)

    want, want_g = f(lhs, rhs)
    monkeypatch.setattr(moe, "_use_gmm", lambda: True)
    monkeypatch.setattr(megablox, "gmm", functools.partial(
        megablox.gmm, interpret=True))
    got, got_g = f(lhs, rhs)
    assert not np.any(np.asarray(got[120:]))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-4)
    for a, b in zip(got_g, want_g):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5,
                                   atol=1e-3)


def test_deepseek_v2_top_k_weights_are_not_renormalised():
    cfg = get_config("deepseek-v2-236b", reduced=True)
    p = _init(init_moe, cfg)(jax.random.PRNGKey(0))
    x = _normal(1, (32, cfg.d_model))
    gates, ids, _, _ = jax.jit(moe._route, static_argnums=1)(p, cfg, x)
    want = jax.jit(lambda x, router, ids: 16.0 * jnp.take_along_axis(
        jax.nn.softmax(x @ router, axis=-1), ids, axis=-1))(
            x, p["router"], ids)
    np.testing.assert_allclose(np.asarray(gates), np.asarray(want),
                               rtol=1e-5)
    assert float(jnp.max(jnp.abs(jnp.sum(gates, -1) - 16.0))) > 1.0
