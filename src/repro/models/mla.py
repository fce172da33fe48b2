"""Multi-head Latent Attention (DeepSeek-V2/V3).

Keys/values are factored through a low-rank latent, and queries through
one too unless ``q_lora_rank`` is None (then one direct projection); the decode
cache stores ONLY the compressed kv-latent (kv_lora=512) plus the shared
rope key (64) per token — independent of the 128 heads — and decode runs
with *weight absorption*: scores are computed directly in latent space
(q_nope absorbed through W_uk, outputs through W_uv), so a 32k-token cache
is 576 floats/token instead of 128·(192+128) = 40960. This is the paper's
"ship the small thing, reconstruct at the consumer" pattern applied to
attention state, and it is what makes deepseek-v2 decode memory-feasible in
the dry-run.
"""

from __future__ import annotations

import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .attention import NEG_INF
from .layers import apply_norm
from .rope import apply_rope


def init_mla(cfg, key, dtype) -> Tuple[Dict, Dict]:
    m = cfg.mla
    d, H = cfg.d_model, cfg.n_heads
    qh = m.qk_nope_head_dim + m.qk_rope_head_dim
    ks = jax.random.split(key, 6)
    sc = lambda n: float(1.0 / np.sqrt(n))
    if m.q_lora_rank is None:          # queries projected directly
        p = {"w_q": jax.random.normal(ks[0], (d, H * qh), dtype) * sc(d)}
        s = {"w_q": ("embed", "heads")}
    else:
        p = {"w_dq": jax.random.normal(ks[0], (d, m.q_lora_rank), dtype) * sc(d),
             "w_uq": jax.random.normal(ks[1], (m.q_lora_rank, H * qh), dtype) * sc(m.q_lora_rank),
             "q_norm": {"scale": jnp.ones((m.q_lora_rank,), jnp.float32)}}
        s = {"w_dq": ("embed", "lora"), "w_uq": ("lora", "heads"),
             "q_norm": {"scale": (None,)}}
    p.update({
        "w_dkv": jax.random.normal(ks[2], (d, m.kv_lora_rank + m.qk_rope_head_dim), dtype) * sc(d),
        "w_uk": jax.random.normal(ks[3], (m.kv_lora_rank, H * m.qk_nope_head_dim), dtype) * sc(m.kv_lora_rank),
        "w_uv": jax.random.normal(ks[4], (m.kv_lora_rank, H * m.v_head_dim), dtype) * sc(m.kv_lora_rank),
        "wo": jax.random.normal(ks[5], (H * m.v_head_dim, d), dtype) * sc(H * m.v_head_dim),
        "kv_norm": {"scale": jnp.ones((m.kv_lora_rank,), jnp.float32)},
    })
    s.update({
        "w_dkv": ("embed", "lora"), "w_uk": ("lora", "heads"),
        "w_uv": ("lora", "heads"), "wo": ("heads", "embed"),
        "kv_norm": {"scale": (None,)},
    })
    return p, s


def _queries(p, cfg, x, positions):
    m = cfg.mla
    b, s, _ = x.shape
    H = cfg.n_heads
    qh = m.qk_nope_head_dim + m.qk_rope_head_dim
    if "w_q" in p:
        q = jnp.einsum("bsd,dh->bsh", x, p["w_q"])
    else:
        cq = apply_norm(p["q_norm"], jnp.einsum("bsd,dr->bsr", x, p["w_dq"]),
                        "rms")
        q = jnp.einsum("bsr,rh->bsh", cq, p["w_uq"])
    q = q.reshape(b, s, H, qh)
    q_nope = q[..., :m.qk_nope_head_dim]
    q_rope = apply_rope(q[..., m.qk_nope_head_dim:], positions,
                        cfg.rope_theta)
    return q_nope, q_rope


def _latents(p, cfg, x, positions):
    m = cfg.mla
    ckv_full = jnp.einsum("bsd,dr->bsr", x, p["w_dkv"])
    ckv = apply_norm(p["kv_norm"], ckv_full[..., :m.kv_lora_rank], "rms")
    k_rope = apply_rope(ckv_full[..., None, m.kv_lora_rank:], positions,
                        cfg.rope_theta)[:, :, 0]   # single shared rope head
    return ckv, k_rope


def _mla_attend_naive(cfg, q_nope, q_rope, k_nope, k_rope, v, positions,
                      k_positions=None):
    m = cfg.mla
    k_positions = positions if k_positions is None else k_positions
    scale = 1.0 / np.sqrt(m.qk_nope_head_dim + m.qk_rope_head_dim)
    scores = (jnp.einsum("bshn,bthn->bhst", q_nope, k_nope,
                         preferred_element_type=jnp.float32)
              + jnp.einsum("bshr,btr->bhst", q_rope, k_rope,
                           preferred_element_type=jnp.float32)) * scale
    causal = k_positions[:, None, :] <= positions[:, :, None]
    scores = jnp.where(causal[:, None, :, :], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    return jnp.einsum("bhst,bthv->bshv", probs, v)


def _mla_attend_chunked(cfg, q_nope, q_rope, k_nope, k_rope, v, positions,
                        block: int):
    """Query blocks of ``block`` rows, each against the keys up to its last
    row, so no block above the diagonal is formed. Each block runs under
    ``jax.checkpoint``: a backward pass holds one block's scores at a
    time, not the whole [s, s] triangle. (Tiles of keys with an online
    softmax held every tile's scores of a layer in its backward pass;
    with a checkpoint per query block they still took 4.4x as long to
    compile at 8,192 positions.)"""
    s = q_nope.shape[1]
    bq = min(block, s)
    if s % bq or k_nope.shape[1] != s:
        return _mla_attend_naive(cfg, q_nope, q_rope, k_nope, k_rope, v,
                                 positions)
    one = jax.checkpoint(functools.partial(_mla_attend_naive, cfg))
    out = []
    for iq in range(s // bq):
        sl, ks = slice(iq * bq, (iq + 1) * bq), slice(0, (iq + 1) * bq)
        out.append(one(q_nope[:, sl], q_rope[:, sl], k_nope[:, ks],
                       k_rope[:, ks], v[:, ks], positions[:, sl],
                       positions[:, ks]))
    return jnp.concatenate(out, axis=1)


def mla_full(p: Dict, cfg, spec, x: jax.Array, positions: jax.Array,
             make_cache: Optional[int] = None
             ) -> Tuple[jax.Array, Optional[Dict]]:
    """Train/prefill: materialized keys/values (matmul-rich, MXU-friendly)."""
    with jax.named_scope("mla"):
        return _mla_full(p, cfg, x, positions, make_cache)


def _mla_full(p, cfg, x, positions, make_cache):
    m = cfg.mla
    b, s, _ = x.shape
    H = cfg.n_heads
    q_nope, q_rope = _queries(p, cfg, x, positions)
    ckv, k_rope = _latents(p, cfg, x, positions)
    k_nope = jnp.einsum("bsr,rh->bsh", ckv, p["w_uk"]) \
        .reshape(b, s, H, m.qk_nope_head_dim)
    v = jnp.einsum("bsr,rh->bsh", ckv, p["w_uv"]) \
        .reshape(b, s, H, m.v_head_dim)
    if cfg.attn_impl == "chunked":
        out = _mla_attend_chunked(cfg, q_nope, q_rope, k_nope, k_rope, v,
                                  positions, cfg.attn_block)
    else:
        out = _mla_attend_naive(cfg, q_nope, q_rope, k_nope, k_rope, v,
                                positions)
    out = out.reshape(b, s, -1)
    y = jnp.einsum("bsh,hd->bsd", out, p["wo"])
    cache = None
    if make_cache is not None:
        cache = init_mla_cache(b, make_cache, m, ckv.dtype)
        cache = mla_cache_append(cache, ckv, k_rope, positions)
    return y, cache


def init_mla_cache(b: int, capacity: int, m, dtype) -> Dict[str, jax.Array]:
    return {
        "ckv": jnp.zeros((b, capacity, m.kv_lora_rank), dtype),
        "krope": jnp.zeros((b, capacity, m.qk_rope_head_dim), dtype),
        "pos": jnp.full((b, capacity), -1, jnp.int32),
        "idx": jnp.zeros((), jnp.int32),
    }


def mla_cache_append(cache, ckv, k_rope, positions):
    C = cache["ckv"].shape[1]
    s = ckv.shape[1]
    slots = (cache["idx"] + jnp.arange(s, dtype=jnp.int32)) % C
    return {
        "ckv": cache["ckv"].at[:, slots].set(ckv),
        "krope": cache["krope"].at[:, slots].set(k_rope),
        "pos": cache["pos"].at[:, slots].set(positions.astype(jnp.int32)),
        "idx": cache["idx"] + s,
    }


def mla_decode(p: Dict, cfg, spec, x: jax.Array, positions: jax.Array,
               cache: Dict) -> Tuple[jax.Array, Dict]:
    """Weight-absorbed decode over the latent cache (576 B-ish per token)."""
    m = cfg.mla
    b = x.shape[0]
    H = cfg.n_heads
    q_nope, q_rope = _queries(p, cfg, x, positions)          # [b,1,H,·]
    ckv, k_rope = _latents(p, cfg, x, positions)
    cache = mla_cache_append(cache, ckv, k_rope, positions)
    w_uk = p["w_uk"].reshape(m.kv_lora_rank, H, m.qk_nope_head_dim)
    q_lat = jnp.einsum("bshn,rhn->bshr", q_nope, w_uk)       # absorb W_uk
    scale = 1.0 / np.sqrt(m.qk_nope_head_dim + m.qk_rope_head_dim)
    scores = (jnp.einsum("bshr,btr->bhst", q_lat, cache["ckv"],
                         preferred_element_type=jnp.float32)
              + jnp.einsum("bshr,btr->bhst", q_rope, cache["krope"],
                           preferred_element_type=jnp.float32)) * scale
    valid = (cache["pos"][:, None, :] >= 0) & (cache["pos"][:, None, :]
                                               <= positions[:, :, None])
    scores = jnp.where(valid[:, None, :, :], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(x.dtype)
    out_lat = jnp.einsum("bhst,btr->bshr", probs, cache["ckv"])
    w_uv = p["w_uv"].reshape(m.kv_lora_rank, H, m.v_head_dim)
    out = jnp.einsum("bshr,rhv->bshv", out_lat, w_uv).reshape(b, 1, -1)
    y = jnp.einsum("bsh,hd->bsd", out, p["wo"])
    return y, cache
