"""Mixture-of-Experts: sort-based dispatch, two execution paths.

``global`` (default, mesh-free): one sorted-scatter dispatch over the whole
token space. Correct everywhere, but under SPMD the data-dependent global
gather/scatter forces GSPMD to replicate the flat token tensors (observed:
157 GB/chip/layer of fp32 all-reduce on mixtral train_4k — the §Perf log's
baseline pathology).

``local`` (mesh present): shard_map local dispatch — the production path.
Tokens never leave their shard except through explicit, minimal
collectives:

* EP regime (num_experts % model-axis == 0 — deepseek 160, jamba 16): each
  (data, model) shard dispatches a DISJOINT token slice, routes it to the
  expert-owning model shards with one tiled all-to-all, computes its own
  experts at full width, reverses the all-to-all, combines locally, and
  all-gathers the token outputs over the model axis.
* TP regime (mixtral's 8 experts on a 16-way axis): every expert's FFN is
  width-sharded over the model axis; dispatch is model-replicated and the
  combined token output is one psum.

FSDP (embed-dim) weight shards are all-gathered explicitly (ZeRO-3), and
capacity is per-shard (standard practice; a straggler/locality win — noted
in DESIGN.md). The router is replicated (it is d·E ≪ anything).

Shared experts (deepseek) run densely outside the shard_map.

The Switch-style load-balance auxiliary loss is returned by both paths.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import PartitionSpec as P

from .hints import current_rules


def init_moe(cfg, key, dtype) -> Tuple[Dict, Dict]:
    m = cfg.moe
    d = cfg.d_model
    ks = jax.random.split(key, 5)
    sc_in, sc_out = float(1.0 / np.sqrt(d)), float(1.0 / np.sqrt(m.expert_d_ff))
    gated = cfg.act in ("swiglu", "geglu")
    p = {
        "router": jax.random.normal(ks[0], (d, m.num_experts), jnp.float32) * sc_in,
        "wi": jax.random.normal(ks[1], (m.num_experts, d, m.expert_d_ff), dtype) * sc_in,
        "wo": jax.random.normal(ks[2], (m.num_experts, m.expert_d_ff, d), dtype) * sc_out,
    }
    s = {
        "router": (None, None),            # replicated: d·E is tiny
        "wi": ("expert", "embed", "mlp"),
        "wo": ("expert", "mlp", "embed"),
    }
    if gated:
        p["wg"] = jax.random.normal(ks[3], (m.num_experts, d, m.expert_d_ff), dtype) * sc_in
        s["wg"] = ("expert", "embed", "mlp")
    if m.num_shared_experts:
        ff_sh = m.num_shared_experts * m.shared_d_ff
        kk = jax.random.split(ks[4], 3)
        p["shared"] = {
            "wi": jax.random.normal(kk[0], (d, ff_sh), dtype) * sc_in,
            "wg": jax.random.normal(kk[1], (d, ff_sh), dtype) * sc_in,
            "wo": jax.random.normal(kk[2], (ff_sh, d), dtype) * float(1.0 / np.sqrt(ff_sh)),
        }
        s["shared"] = {"wi": ("embed", "mlp"), "wg": ("embed", "mlp"),
                       "wo": ("mlp", "embed")}
    return p, s


def _act(h, g, act: str):
    if act == "swiglu":
        return jax.nn.silu(g) * h
    if act == "geglu":
        return jax.nn.gelu(g) * h
    return jax.nn.gelu(h)


# ---------------------------------------------------------------------------
# Shared core: local sorted-scatter dispatch + combine (shape-local)
# ---------------------------------------------------------------------------

def _route(router, cfg, xf):
    """Returns (gate_vals [N,K], expert_ids [N,K], aux scalar)."""
    m = cfg.moe
    N = xf.shape[0]
    logits = jnp.einsum("nd,de->ne", xf.astype(jnp.float32), router)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, expert_ids = jax.lax.top_k(probs, m.top_k)
    gate_vals = gate_vals / jnp.sum(gate_vals, axis=-1, keepdims=True)
    counts = jnp.zeros((m.num_experts,), jnp.float32) \
        .at[expert_ids.reshape(-1)].add(1.0)
    frac = counts / (N * m.top_k)
    aux = m.num_experts * jnp.sum(frac * jnp.mean(probs, axis=0))
    return gate_vals, expert_ids, aux


def _dispatch_table(expert_ids, E: int, capacity: int):
    """Sorted-scatter table [E, C] of flat (token·K) indices; sentinel M."""
    N, K = expert_ids.shape
    M = N * K
    flat_experts = expert_ids.reshape(M)
    sort_idx = jnp.argsort(flat_experts)                 # stable
    sorted_experts = flat_experts[sort_idx]
    counts_i = jnp.zeros((E,), jnp.int32).at[flat_experts].add(1)
    starts = jnp.cumsum(counts_i) - counts_i             # exclusive cumsum
    pos_in_expert = jnp.arange(M, dtype=jnp.int32) - starts[sorted_experts]
    slot = jnp.where(pos_in_expert < capacity, pos_in_expert, capacity)
    table = jnp.full((E, capacity), M, jnp.int32)
    table = table.at[sorted_experts, slot].set(sort_idx.astype(jnp.int32),
                                               mode="drop")
    return table, M


def _gather_tokens(xf, table, K: int):
    N, d = xf.shape
    x_pad = jnp.concatenate([xf, jnp.zeros((1, d), xf.dtype)], axis=0)
    return x_pad[table // K]                             # [E, C, d]


def _combine_tokens(y_e, gate_vals, table, N: int, K: int):
    M = N * K
    d = y_e.shape[-1]
    gates_flat = jnp.concatenate([gate_vals.reshape(M), jnp.zeros((1,))])
    w_e = gates_flat[table].astype(y_e.dtype)
    out_flat = jnp.zeros((M + 1, d), y_e.dtype) \
        .at[table.reshape(-1)].add((y_e * w_e[..., None]).reshape(-1, d))
    return jnp.sum(out_flat[:M].reshape(N, K, d), axis=1)


def _expert_ffn(p, cfg, x_e):
    h = jnp.einsum("ecd,edf->ecf", x_e, p["wi"])
    g = jnp.einsum("ecd,edf->ecf", x_e, p["wg"]) if "wg" in p else None
    h = _act(h, g, cfg.act)
    return jnp.einsum("ecf,efd->ecd", h, p["wo"])


def _shared_experts(p, cfg, xf):
    sp = p["shared"]
    hs = jnp.einsum("nd,df->nf", xf, sp["wi"])
    gs = jnp.einsum("nd,df->nf", xf, sp["wg"])
    return jnp.einsum("nf,fd->nd", _act(hs, gs, cfg.act), sp["wo"])


# ---------------------------------------------------------------------------
# Global path (mesh-free reference)
# ---------------------------------------------------------------------------

def _apply_moe_global(p: Dict, cfg, x: jax.Array,
                      capacity: Optional[int] = None
                      ) -> Tuple[jax.Array, jax.Array]:
    m = cfg.moe
    b, s, d = x.shape
    N = b * s
    xf = x.reshape(N, d)
    gate_vals, expert_ids, aux = _route(p["router"], cfg, xf)
    if capacity is None:
        capacity = max(1, int(math.ceil(N * m.top_k / m.num_experts
                                        * m.capacity_factor)))
    table, _ = _dispatch_table(expert_ids, m.num_experts, capacity)
    x_e = _gather_tokens(xf, table, m.top_k)
    y_e = _expert_ffn(p, cfg, x_e)
    y = _combine_tokens(y_e, gate_vals, table, N, m.top_k)
    if m.num_shared_experts:
        y = y + _shared_experts(p, cfg, xf)
    return y.reshape(b, s, d), aux


# ---------------------------------------------------------------------------
# Local path (shard_map, mesh present)
# ---------------------------------------------------------------------------

def _fsdp_axes(rules_map, dim: int, mesh) -> Optional[Tuple[str, ...]]:
    """Mirror dist/shardings: first FSDP candidate whose size divides dim."""
    default = [("pod", "data"), ("data",)] if "pod" in mesh.shape \
        else [("data",)]
    cands = rules_map.get("fsdp_candidates", default)
    for c in cands:
        size = 1
        for a in c:
            size *= mesh.shape[a]
        if dim % size == 0:
            return c
    return None


def _apply_moe_local(p: Dict, cfg, x: jax.Array, ctx
                     ) -> Tuple[jax.Array, jax.Array]:
    mesh, rules = ctx
    m = cfg.moe
    b, s, d = x.shape
    dp = rules["tokens"]
    dp = (dp,) if isinstance(dp, str) else tuple(dp)
    G = mesh.shape["model"]
    E = m.num_experts
    ep = (E % G == 0)
    n_dp = 1
    for a in dp:
        n_dp *= mesh.shape[a]
    b_loc = b // n_dp
    if b_loc == 0 or (ep and (b_loc * s) % G != 0):
        return _apply_moe_global(p, cfg, x)

    fsdp = _fsdp_axes(rules, d, mesh)
    # in_specs mirroring dist/shardings greedy assignment:
    if ep:
        wi_spec = P("model", fsdp if fsdp else None, None)
        wo_spec = P("model", None, fsdp if fsdp else None)
    else:
        tp_ok = (m.expert_d_ff % G == 0)
        if not tp_ok:
            return _apply_moe_global(p, cfg, x)
        wi_spec = P(None, fsdp if fsdp else None, "model")
        wo_spec = P(None, "model", fsdp if fsdp else None)

    def local_fn(xl, router, wi, wg, wo):
        bl, sl, dl = xl.shape
        xf = xl.reshape(-1, d)                            # [N_loc, d]
        N_loc = xf.shape[0]

        # ZeRO-3: explicit FSDP gather of this layer's expert weights
        if fsdp is not None:
            wi_f = jax.lax.all_gather(wi, fsdp, axis=1, tiled=True)
            wg_f = (jax.lax.all_gather(wg, fsdp, axis=1, tiled=True)
                    if wg is not None else None)
            wo_f = jax.lax.all_gather(wo, fsdp, axis=2, tiled=True)
        else:
            wi_f, wg_f, wo_f = wi, wg, wo
        pp = {"wi": wi_f, "wo": wo_f}
        if wg_f is not None:
            pp["wg"] = wg_f

        if ep:
            # each model shard dispatches a disjoint token slice
            chunk = N_loc // G
            i = jax.lax.axis_index("model")
            xme = jax.lax.dynamic_slice_in_dim(xf, i * chunk, chunk, 0)
            gate_vals, expert_ids, aux = _route(router, cfg, xme)
            cap = max(1, int(math.ceil(chunk * m.top_k / E
                                       * m.capacity_factor)))
            table, _ = _dispatch_table(expert_ids, E, cap)
            x_e = _gather_tokens(xme, table, m.top_k)     # [E, cap, d]
            # route to expert owners: one tiled all-to-all over model
            xa = jax.lax.all_to_all(x_e, "model", split_axis=0,
                                    concat_axis=1, tiled=True)
            y_own = _expert_ffn(pp, cfg, xa)              # [E/G, cap·G, d]
            y_e = jax.lax.all_to_all(y_own, "model", split_axis=1,
                                     concat_axis=0, tiled=True)
            y_me = _combine_tokens(y_e, gate_vals, table, chunk, m.top_k)
            y = jax.lax.all_gather(y_me, "model", axis=0, tiled=True)
            aux = jax.lax.psum(aux, dp + ("model",)) / (n_dp * G)
        else:
            # TP experts: model-replicated dispatch, width-sharded FFN,
            # one token-space psum
            gate_vals, expert_ids, aux = _route(router, cfg, xf)
            cap = max(1, int(math.ceil(N_loc * m.top_k / E
                                       * m.capacity_factor)))
            table, _ = _dispatch_table(expert_ids, E, cap)
            x_e = _gather_tokens(xf, table, m.top_k)
            y_e = _expert_ffn(pp, cfg, x_e)               # partial over f
            y = _combine_tokens(y_e, gate_vals, table, N_loc, m.top_k)
            y = jax.lax.psum(y, "model")
            aux = jax.lax.psum(aux, dp) / n_dp
        return y.reshape(bl, sl, dl), aux

    in_specs = (P(dp if len(dp) > 1 else dp[0], None, None),
                P(None, None), wi_spec,
                (wi_spec if "wg" in p else None), wo_spec)
    out_specs = (P(dp if len(dp) > 1 else dp[0], None, None), P())
    y, aux = shard_map(local_fn, mesh=mesh, in_specs=in_specs,
                       out_specs=out_specs, check_vma=False)(
        x, p["router"], p["wi"], p.get("wg"), p["wo"])

    if m.num_shared_experts:
        xf = x.reshape(b * s, d)
        y = y + _shared_experts(p, cfg, xf).reshape(b, s, d)
    return y, aux


def apply_moe(p: Dict, cfg, x: jax.Array,
              capacity: Optional[int] = None
              ) -> Tuple[jax.Array, jax.Array]:
    """x [b, s, d] → (y [b, s, d], aux_loss scalar)."""
    ctx = current_rules()
    if getattr(cfg, "moe_impl", "global") == "local" and ctx is not None:
        return _apply_moe_local(p, cfg, x, ctx)
    return _apply_moe_global(p, cfg, x, capacity)
