"""Per-architecture smoke tests (REDUCED configs): one forward/train step
on CPU asserting output shapes and finiteness, plus prefill→decode
consistency (decode over a prefilled cache must reproduce the full-seq
forward logits at each position)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCH_IDS, get_config, smoke_batch
from repro.models import (decode_step, forward, init_model, prefill,
                          train_loss)


def _seq_for(cfg):
    # SSD needs seq % chunk == 0; prefix mode needs room for the prefix
    if cfg.ssm is not None:
        return max(16, cfg.ssm.chunk * 2)
    if cfg.input_mode == "tokens+prefix":
        return cfg.prefix_len + 8
    return 16


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_forward_shapes_and_finite(arch):
    cfg = get_config(arch, reduced=True)
    params, _specs = init_model(cfg, jax.random.PRNGKey(0))
    b, s = 2, _seq_for(cfg)
    batch = smoke_batch(cfg, b=b, s=s)
    logits, aux = jax.jit(lambda p, x: forward(cfg, p, x, remat=False))(
        params, batch)
    s_out = s - (cfg.prefix_len if cfg.input_mode == "tokens+prefix" else 0) \
        + (cfg.prefix_len if cfg.input_mode == "tokens+prefix" else 0)
    assert logits.shape == (b, s, cfg.vocab)
    assert bool(jnp.all(jnp.isfinite(logits)))
    assert bool(jnp.isfinite(aux))


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_train_step_decreases_loss(arch):
    """One SGD step on the smoke batch must reduce the loss (gradients flow
    through every block type)."""
    cfg = get_config(arch, reduced=True)
    params, _ = init_model(cfg, jax.random.PRNGKey(1))
    batch = smoke_batch(cfg, b=2, s=_seq_for(cfg))

    loss_fn = lambda p: train_loss(cfg, p, batch, remat=False)
    loss0, grads = jax.jit(jax.value_and_grad(loss_fn))(params)
    assert bool(jnp.isfinite(loss0))
    gnorm = jnp.sqrt(sum(jnp.sum(g.astype(jnp.float32) ** 2)
                         for g in jax.tree_util.tree_leaves(grads)))
    assert bool(gnorm > 0), "no gradient signal"
    lr = 2e-2 / max(1e-6, float(gnorm))
    params2 = jax.tree_util.tree_map(
        lambda p, g: p - lr * g.astype(p.dtype), params, grads)
    loss1 = jax.jit(lambda p: train_loss(cfg, p, batch, remat=False))(params2)
    assert float(loss1) < float(loss0), (float(loss0), float(loss1))


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_prefill_then_decode_matches_full_forward(arch):
    """Serve path correctness: logits from incremental decode equal the
    full-sequence forward logits (same params, same tokens)."""
    cfg = get_config(arch, reduced=True)
    if cfg.input_mode == "tokens+prefix":
        pytest.skip("prefix mode exercises decode via the text-only path")
    params, _ = init_model(cfg, jax.random.PRNGKey(2))
    b = 2
    s = _seq_for(cfg)
    batch = smoke_batch(cfg, b=b, s=s, train=False)

    full_logits, _aux = forward(cfg, params, dict(batch), remat=False)

    split = s // 2
    if cfg.ssm is not None:   # SSD prefill needs chunk-aligned length
        split = (split // cfg.ssm.chunk) * cfg.ssm.chunk or cfg.ssm.chunk
    if cfg.input_mode == "embeds":
        prompt = {"embeds": batch["embeds"][:, :split]}
        rest = [batch["embeds"][:, i:i + 1] for i in range(split, s)]
    else:
        prompt = {"tokens": batch["tokens"][:, :split]}
        rest = [batch["tokens"][:, i:i + 1] for i in range(split, s)]

    logits_p, caches = prefill(cfg, params, prompt, max_len=s)
    np.testing.assert_allclose(np.asarray(logits_p[:, 0]),
                               np.asarray(full_logits[:, split - 1]),
                               rtol=2e-2, atol=2e-2)
    for k, tok in enumerate(rest):
        pos = jnp.full((b, 1), split + k, jnp.int32)
        logits_d, caches = decode_step(cfg, params, tok, pos, caches)
        np.testing.assert_allclose(
            np.asarray(logits_d[:, 0]),
            np.asarray(full_logits[:, split + k]),
            rtol=2e-2, atol=2e-2,
            err_msg=f"{arch}: decode step {k} diverged from full forward")


def test_param_counts_sane():
    """Full-config parameter counts are in the published ballpark."""
    expect = {
        "mixtral-8x22b": (141e9, 0.35),
        "deepseek-v2-236b": (236e9, 0.35),
        "qwen2-1.5b": (1.5e9, 0.45),
        "qwen1.5-0.5b": (0.5e9, 0.45),
        "gemma2-27b": (27e9, 0.40),
        "mamba2-130m": (130e6, 0.45),
        "jamba-v0.1-52b": (52e9, 0.40),
        "stablelm-1.6b": (1.6e9, 0.45),
        "phi-3-vision-4.2b": (4.2e9, 0.45),
        "musicgen-large": (3.3e9, 0.75),
        "kanana-2-30b-a3b": (30e9, 0.10),
    }
    for arch, (want, tol) in expect.items():
        cfg = get_config(arch)
        total, active = cfg.param_counts()
        assert abs(total - want) / want < tol, (arch, total, want)
        assert active <= total
