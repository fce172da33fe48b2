"""Composable decoder stack over ``LayerSpec`` layouts.

One implementation serves all 10 assigned architectures:

* blocks: pre-norm attention/MLA/SSD + dense-or-MoE MLP (+ gemma2-style
  post-norms), assembled per the config's layer layout;
* the stack is executed as ``lax.scan`` over *stacked* layer parameters,
  grouped by ``layout_groups`` (smallest repeating super-block) so the HLO
  contains each distinct block body once — bounded compile time at 512
  devices and bounded HLO for the roofline parser;
* ``jax.checkpoint`` (remat) around each super-block in training;
* three entry points: ``train_loss`` (full seq), ``prefill`` (full seq →
  caches), ``decode_step`` (one token against caches).

Modality frontends are STUBS per the assignment: ``input_mode`` selects
token embedding, raw embeddings (musicgen frames), or token+prefix
embeddings (phi-3-vision patches).
"""

from __future__ import annotations

import functools
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import attention as attn_mod
from . import mla as mla_mod
from . import moe as moe_mod
from . import ssm as ssm_mod
from .config import LayerSpec, ModelConfig, layout_groups
from .hints import hint
from .layers import (apply_mlp, apply_norm, cross_entropy, embed_tokens,
                     init_embedding, init_mlp, init_norm, lm_logits,
                     sinusoidal_positions)

AUX_LOSS_WEIGHT = 0.01


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------

def _init_layer(cfg: ModelConfig, spec: LayerSpec, key, dtype
                ) -> Tuple[Dict, Dict]:
    ks = jax.random.split(key, 4)
    p: Dict[str, Any] = {}
    s: Dict[str, Any] = {}
    p["norm1"], s["norm1"] = init_norm(cfg, cfg.d_model)
    if spec.kind == "attn":
        p["mix"], s["mix"] = attn_mod.init_attention(cfg, ks[0], dtype)
    elif spec.kind == "mla":
        p["mix"], s["mix"] = mla_mod.init_mla(cfg, ks[0], dtype)
    elif spec.kind == "ssm":
        p["mix"], s["mix"] = ssm_mod.init_ssm(cfg, ks[0], dtype)
    else:
        raise ValueError(spec.kind)
    if spec.mlp == "dense":
        p["norm2"], s["norm2"] = init_norm(cfg, cfg.d_model)
        p["mlp"], s["mlp"] = init_mlp(cfg, ks[1], cfg.d_model, cfg.d_ff, dtype)
    elif spec.mlp == "moe":
        p["norm2"], s["norm2"] = init_norm(cfg, cfg.d_model)
        p["mlp"], s["mlp"] = moe_mod.init_moe(cfg, ks[1], dtype)
    elif spec.mlp != "none":   # "none": pure mixer block (mamba2)
        raise ValueError(spec.mlp)
    if cfg.post_norms:
        p["post_attn"], s["post_attn"] = init_norm(cfg, cfg.d_model)
        p["post_mlp"], s["post_mlp"] = init_norm(cfg, cfg.d_model)
    return p, s


def init_model(cfg: ModelConfig, key) -> Tuple[Dict, Dict]:
    """Returns (params, logical_pspecs); layer params are stacked per group
    with a leading `layers` axis."""
    dtype = jnp.dtype(cfg.dtype)
    groups = layout_groups(cfg.default_layout())
    k_emb, k_rest = jax.random.split(key)
    params: Dict[str, Any] = {}
    specs: Dict[str, Any] = {}
    params["embed"], specs["embed"] = init_embedding(cfg, k_emb, dtype)
    params["final_norm"], specs["final_norm"] = init_norm(cfg, cfg.d_model)

    params["groups"] = []
    specs["groups"] = []
    gkeys = jax.random.split(k_rest, len(groups))
    for (block, repeats), gk in zip(groups, gkeys):
        lkeys = jax.random.split(gk, repeats)

        def init_block(k, block=block):
            parts = []
            for li, spec in enumerate(block):
                pk = jax.random.fold_in(k, li)
                p, _ = _init_layer(cfg, spec, pk, dtype)
                parts.append(p)
            return parts

        stacked = jax.vmap(init_block)(lkeys)
        # spec tree (same for every repeat): prepend scan ("layers") axis
        sub_specs = []
        for li, spec in enumerate(block):
            _, s = _init_layer(cfg, spec, jax.random.PRNGKey(0), dtype)
            sub_specs.append(jax.tree_util.tree_map(
                lambda ax: ("layers",) + tuple(ax), s,
                is_leaf=lambda t: isinstance(t, tuple)))
        params["groups"].append(stacked)
        specs["groups"].append(sub_specs)
    return params, specs


# ---------------------------------------------------------------------------
# Block application
# ---------------------------------------------------------------------------

def _apply_block(cfg: ModelConfig, spec: LayerSpec, p: Dict, x: jax.Array,
                 positions: jax.Array, mode: str,
                 cache: Optional[Dict], cache_capacity: Optional[int]
                 ) -> Tuple[jax.Array, Optional[Dict], jax.Array]:
    """One decoder block. Returns (x, new_cache, aux_loss)."""
    aux = jnp.zeros((), jnp.float32)
    x = hint(x, ("batch", None, None))
    h = apply_norm(p["norm1"], x, cfg.norm)
    new_cache = None
    with jax.named_scope("ssm" if spec.kind == "ssm" else "attention"):
        if spec.kind == "attn":
            if mode == "decode":
                y, new_cache = attn_mod.attend_decode(p["mix"], cfg, spec,
                                                      h, positions, cache)
            else:
                y, new_cache = attn_mod.attend_full(
                    p["mix"], cfg, spec, h, positions,
                    make_cache=cache_capacity if mode == "prefill" else None)
        elif spec.kind == "mla":
            if mode == "decode":
                y, new_cache = mla_mod.mla_decode(p["mix"], cfg, spec, h,
                                                  positions, cache)
            else:
                y, new_cache = mla_mod.mla_full(
                    p["mix"], cfg, spec, h, positions,
                    make_cache=cache_capacity if mode == "prefill" else None)
        else:  # ssm
            if mode == "decode":
                y, new_cache = ssm_mod.ssm_decode(p["mix"], cfg, h, cache)
            else:
                y, new_cache = ssm_mod.ssm_full(
                    p["mix"], cfg, h, make_cache=(mode == "prefill"))
    if cfg.post_norms:
        y = apply_norm(p["post_attn"], y, cfg.norm)
    x = x + y

    if spec.mlp == "none":
        return x, new_cache, aux
    h = apply_norm(p["norm2"], x, cfg.norm)
    with jax.named_scope("mlp" if spec.mlp == "dense" else "moe"):
        if spec.mlp == "dense":
            y = apply_mlp(p["mlp"], h, cfg.act)
        else:
            y, aux = moe_mod.apply_moe(p["mlp"], cfg, h)
    if cfg.post_norms:
        y = apply_norm(p["post_mlp"], y, cfg.norm)
    return x + y, new_cache, aux


def _cache_capacity(cfg: ModelConfig, spec: LayerSpec, max_len: int) -> int:
    if spec.kind == "ssm":
        return 0  # SSM caches are fixed-shape; capacity unused
    if spec.window is not None:
        return min(spec.window, max_len)
    return max_len


# ---------------------------------------------------------------------------
# Stack runner (scan over stacked layer groups)
# ---------------------------------------------------------------------------

def _run_stack(cfg: ModelConfig, params: Dict, x: jax.Array,
               positions: jax.Array, mode: str,
               caches: Optional[List] = None,
               max_len: Optional[int] = None, remat: bool = True
               ) -> Tuple[jax.Array, Optional[List], jax.Array]:
    groups = layout_groups(cfg.default_layout())
    new_caches: List[Any] = []
    aux_total = jnp.zeros((), jnp.float32)

    for gi, (block, repeats) in enumerate(groups):
        stacked = params["groups"][gi]

        def body(x, layer_inputs, block=block):
            layer_params, layer_cache = layer_inputs
            aux_l = jnp.zeros((), jnp.float32)
            outs = []
            for li, spec in enumerate(block):
                c = layer_cache[li] if layer_cache is not None else None
                cap = _cache_capacity(cfg, spec, max_len) if max_len else None
                x, nc, aux = _apply_block(cfg, spec, layer_params[li], x,
                                          positions, mode, c, cap)
                outs.append(nc)
                aux_l = aux_l + aux
            if any(o is not None for o in outs):
                return x, (outs, aux_l)
            return x, (None, aux_l)

        body_fn = jax.checkpoint(body) if (remat and mode == "train") else body
        cache_in = caches[gi] if caches is not None else None
        x, (cache_out, aux_stack) = jax.lax.scan(
            body_fn, x, (stacked, cache_in))
        aux_total = aux_total + jnp.sum(aux_stack)
        new_caches.append(cache_out)

    return x, (new_caches if mode in ("prefill", "decode") else None), aux_total


# ---------------------------------------------------------------------------
# Inputs → hidden states
# ---------------------------------------------------------------------------

def _inputs_to_hidden(cfg: ModelConfig, params: Dict, batch: Dict
                      ) -> Tuple[jax.Array, jax.Array]:
    if cfg.input_mode == "embeds":
        x = batch["embeds"].astype(jnp.dtype(cfg.dtype))
        b, s = x.shape[0], x.shape[1]
        positions = batch.get("positions",
                              jnp.broadcast_to(jnp.arange(s)[None, :], (b, s)))
    elif cfg.input_mode == "tokens+prefix" and "prefix_embeds" in batch:
        prefix = batch["prefix_embeds"].astype(jnp.dtype(cfg.dtype))
        tok = embed_tokens(params["embed"], cfg, batch["tokens"])
        x = jnp.concatenate([prefix, tok], axis=1)
        b, s = x.shape[0], x.shape[1]
        positions = jnp.broadcast_to(jnp.arange(s)[None, :], (b, s))
    else:
        tok = batch["tokens"]
        x = embed_tokens(params["embed"], cfg, tok)
        b, s = x.shape[0], x.shape[1]
        positions = batch.get("positions",
                              jnp.broadcast_to(jnp.arange(s)[None, :], (b, s)))
    if cfg.pos == "sinusoidal":
        x = x + sinusoidal_positions(positions, cfg.d_model, x.dtype)
    x = hint(x, ("batch", None, None))
    return x, positions


# ---------------------------------------------------------------------------
# Public entry points
# ---------------------------------------------------------------------------

def forward(cfg: ModelConfig, params: Dict, batch: Dict,
            remat: bool = True) -> Tuple[jax.Array, jax.Array]:
    """Full-sequence logits (training). Returns (logits, aux_loss)."""
    with jax.named_scope("embed"):
        x, positions = _inputs_to_hidden(cfg, params, batch)
    with jax.named_scope("layers"):
        x, _, aux = _run_stack(cfg, params, x, positions, "train",
                               remat=remat)
    with jax.named_scope("head"):
        x = apply_norm(params["final_norm"], x, cfg.norm)
        return lm_logits(params["embed"], cfg, x), aux


def train_loss(cfg: ModelConfig, params: Dict, batch: Dict,
               remat: bool = True) -> jax.Array:
    logits, aux = forward(cfg, params, batch, remat=remat)
    labels = batch["labels"]
    if cfg.input_mode == "tokens+prefix":
        logits = logits[:, cfg.prefix_len:, :]  # loss on text positions only
    with jax.named_scope("loss"):
        loss = cross_entropy(logits, labels, batch.get("loss_mask"))
    return loss + AUX_LOSS_WEIGHT * aux


def prefill(cfg: ModelConfig, params: Dict, batch: Dict, max_len: int
            ) -> Tuple[jax.Array, List]:
    """Run the prompt; returns (last-position logits, caches)."""
    x, positions = _inputs_to_hidden(cfg, params, batch)
    x, caches, _ = _run_stack(cfg, params, x, positions, "prefill",
                              max_len=max_len, remat=False)
    x = apply_norm(params["final_norm"], x, cfg.norm)
    logits = lm_logits(params["embed"], cfg, x[:, -1:, :])
    return logits, caches


def decode_step(cfg: ModelConfig, params: Dict, tokens: jax.Array,
                pos: jax.Array, caches: List
                ) -> Tuple[jax.Array, List]:
    """One decode step: tokens [b,1] (or embeds [b,1,d]), pos [b,1]."""
    if cfg.input_mode == "embeds":
        batch = {"embeds": tokens, "positions": pos}
    else:
        batch = {"tokens": tokens, "positions": pos}
    x, positions = _inputs_to_hidden(cfg, params, batch)
    x, caches, _ = _run_stack(cfg, params, x, positions, "decode",
                              caches=caches,
                              max_len=int(caches_max_len(caches)))
    x = apply_norm(params["final_norm"], x, cfg.norm)
    return lm_logits(params["embed"], cfg, x), caches


def caches_max_len(caches: List) -> int:
    best = 1
    for group in caches:
        if group is None:
            continue
        for c in group:
            if c is not None and "k" in c:
                best = max(best, c["k"].shape[2])   # [layers,b,C,kv,hd]
            elif c is not None and "ckv" in c:
                best = max(best, c["ckv"].shape[2])
    return best


def init_caches(cfg: ModelConfig, params: Dict, b: int, max_len: int,
                dtype=None) -> List:
    """Fresh (empty) caches shaped like prefill's output — for pure-decode
    dry-runs (decode_32k / long_500k lower serve_step only)."""
    dtype = dtype or jnp.dtype(cfg.dtype)
    groups = layout_groups(cfg.default_layout())
    caches = []
    for block, repeats in groups:
        sub = []
        for spec in block:
            cap = _cache_capacity(cfg, spec, max_len)
            if spec.kind == "attn":
                c = attn_mod.init_kv_cache(b, cap, cfg.n_kv_heads,
                                           cfg.resolved_head_dim(), dtype)
            elif spec.kind == "mla":
                c = mla_mod.init_mla_cache(b, cap, cfg.mla, dtype)
            else:
                c = ssm_mod.init_ssm_cache(cfg, b, dtype)
            sub.append(jax.tree_util.tree_map(
                lambda x: jnp.broadcast_to(x[None], (repeats,) + x.shape), c))
        caches.append(sub)
    return caches
