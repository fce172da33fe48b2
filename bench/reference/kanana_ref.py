"""Plain reference of a DeepSeek-V3-layout decoder (kanana-2-30b-a3b) on
one chip's share of its experts, and its first AdamW training steps, in
float32 with every contraction at ``HIGHEST`` precision. It imports
nothing of the program.

The model, as the published config describes it:

* token embedding; ``first_k_dense_replace`` dense layers, then MoE
  layers; a final RMSNorm and an untied head; mean next-token
  cross-entropy. RMSNorm everywhere with ``rms_norm_eps``.
* Attention (MLA, ``q_lora_rank`` null): per head, q is one projection
  of the normed input into ``qk_nope_head_dim + qk_rope_head_dim``; the
  kv latent is one projection into ``kv_lora_rank`` (RMSNorm'd) plus one
  rope key of ``qk_rope_head_dim`` shared by every head; the latent gives
  each head's no-rope key and value. Rope (theta ``rope_theta``, no
  scaling) rotates adjacent channel pairs of the rope parts
  (``rope_interleave``). Causal softmax of the scores times
  1/sqrt(``qk_head_dim``), then the output projection.
* Dense layers: a SwiGLU MLP of ``intermediate_size``.
* MoE layers: scores sigmoid(x W_r) over all ``router_experts`` of the
  router; the top ``num_experts_per_tok`` chosen on score + selection
  bias; weights the chosen unbiased scores, divided by their sum
  (``norm_topk_prob``) and times ``routed_scaling_factor``. The chip's
  share: each held expert (``held_experts``, ``[first, count]``) is a
  SwiGLU of ``moe_intermediate_size`` computed densely over every token
  and weighted by that token's weight for it (zero where it was not
  chosen); experts held elsewhere add nothing. The shared experts are one
  SwiGLU of ``n_shared_experts x moe_intermediate_size``, added once.

Departures, none of them in the mathematics: parameters are a flat dict
named as the benchmark names them, with layer weights stacked on a
leading axis per kind of layer; ``kv_b_proj`` is held as its key half
(``w_uk``) and value half (``w_uv``), and ``kv_a_proj_with_mqa`` as
``w_dkv``; attention runs in query blocks of ``QUERY_BLOCK`` rows (one
loop, each block against every key with the later keys masked), each
block recomputed in the backward pass, so that 8,192 positions fit on
the chip.

``rounding`` puts the nearest lower precision in its place, for the
control: each contraction's operands, and in the backward pass its
incoming gradient, are rounded to float8 (``qwen_ref.round_fp8``).
"""

from __future__ import annotations

import functools
import json
from typing import Callable, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from . import qwen_ref

QUERY_BLOCK = 512
ATTN_NAMES = ("norm1.scale", "mix.w_q", "mix.w_dkv", "mix.kv_norm.scale",
              "mix.w_uk", "mix.w_uv", "mix.wo", "norm2.scale")
DENSE_NAMES = ATTN_NAMES + ("mlp.wg", "mlp.wi", "mlp.wo")
MOE_NAMES = ATTN_NAMES + ("mlp.router", "mlp.bias", "mlp.wg", "mlp.wi",
                          "mlp.wo", "mlp.shared.wg", "mlp.shared.wi",
                          "mlp.shared.wo")


class Model:
    """The reference model for ``model`` (a configuration file's
    published keys)."""

    def __init__(self, model: dict, rounding: Optional[Callable] = None):
        self.heads = int(model["num_attention_heads"])
        self.nope = int(model["qk_nope_head_dim"])
        self.rope = int(model["qk_rope_head_dim"])
        self.vd = int(model["v_head_dim"])
        self.kv = int(model["kv_lora_rank"])
        self.eps = float(model["rms_norm_eps"])
        self.dense = int(model["first_k_dense_replace"])
        self.theta = float(model["rope_theta"])
        self.k = int(model["num_experts_per_tok"])
        self.experts = int(model["router_experts"])
        self.first = int(model["held_experts"][0])
        self.held = int(model["held_experts"][1])
        self.scale = float(model["routed_scaling_factor"])
        self.renorm = bool(model["norm_topk_prob"])
        self.c = qwen_ref.make_contract(rounding)

    def _rms(self, x, scale):
        var = jnp.mean(x * x, axis=-1, keepdims=True)
        return x * jax.lax.rsqrt(var + self.eps) * scale

    def _rope(self, x):
        s, hd = x.shape[1], x.shape[-1]
        inv = 1.0 / (self.theta ** (np.arange(0, hd, 2, dtype=np.float64)
                                    / hd))
        ang = (jnp.arange(s, dtype=jnp.float32)[:, None]
               * jnp.asarray(inv, jnp.float32))
        sin, cos = jnp.sin(ang)[None, :, None], jnp.cos(ang)[None, :, None]
        x1, x2 = x[..., 0::2], x[..., 1::2]
        return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                         axis=-1).reshape(x.shape)

    def _attend(self, qn, qr, kn, kr, v, q0):
        """One query block (rows from ``q0``) against every key, the keys
        after each row masked out."""
        c = self.c
        bq, t = qn.shape[1], kn.shape[1]
        scores = (c("bshn,bthn->bhst", qn, kn)
                  + c("bshr,btr->bhst", qr, kr)) / np.sqrt(self.nope
                                                          + self.rope)
        causal = (jnp.arange(t)[None, :]
                  <= q0 + jnp.arange(bq)[:, None])
        scores = jnp.where(causal, scores, -jnp.inf)
        return c("bhst,bthv->bshv", jax.nn.softmax(scores, axis=-1), v)

    def _attention(self, x, p):
        """``x`` plus the attention block's output."""
        c = self.c
        b, s, _ = x.shape
        H = self.heads
        h = self._rms(x, p["norm1.scale"])
        q = c("bsd,dh->bsh", h, p["mix.w_q"]).reshape(
            b, s, H, self.nope + self.rope)
        qn, qr = q[..., :self.nope], self._rope(q[..., self.nope:])
        kv = c("bsd,dr->bsr", h, p["mix.w_dkv"])
        ckv = self._rms(kv[..., :self.kv], p["mix.kv_norm.scale"])
        kr = self._rope(kv[..., None, self.kv:])[:, :, 0]
        kn = c("bsr,rh->bsh", ckv, p["mix.w_uk"]).reshape(b, s, H,
                                                          self.nope)
        v = c("bsr,rh->bsh", ckv, p["mix.w_uv"]).reshape(b, s, H, self.vd)
        bq = QUERY_BLOCK if s % QUERY_BLOCK == 0 else s
        nb = s // bq

        def blocks(a):                      # [b, s, ...] -> [nb, b, bq, ...]
            return jnp.moveaxis(a.reshape((b, nb, bq) + a.shape[2:]), 1, 0)

        o = jax.lax.map(jax.checkpoint(
            lambda a: self._attend(a[0], a[1], kn, kr, v, a[2])),
            (blocks(qn), blocks(qr), jnp.arange(nb) * bq))
        o = jnp.moveaxis(o, 0, 1).reshape(b, s, H * self.vd)
        return x + c("bsh,hd->bsd", o, p["mix.wo"])

    def _swiglu(self, h, wg, wi, wo):
        c = self.c
        return c("bsf,fd->bsd", jax.nn.silu(c("bsd,df->bsf", h, wg))
                 * c("bsd,df->bsf", h, wi), wo)

    def route(self, h, router, bias):
        """``(weights [b, s, K], chosen experts [b, s, K], chosen without
        the bias)`` for normed inputs ``h``."""
        scores = jax.nn.sigmoid(self.c("bsd,de->bse", h, router))
        _, idx = jax.lax.top_k(scores + bias, self.k)
        _, unbiased = jax.lax.top_k(scores, self.k)
        w = jnp.take_along_axis(scores, idx, axis=-1)
        if self.renorm:
            w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
        return w * self.scale, idx, unbiased

    def _dense(self, x, p):
        x = self._attention(x, p)
        h = self._rms(x, p["norm2.scale"])
        return x + self._swiglu(h, p["mlp.wg"], p["mlp.wi"], p["mlp.wo"])

    def moe_mlp(self, h, p):
        """The MoE MLP on normed inputs ``h``: ``(the held experts' part
        plus the shared experts, pairs choosing each expert [E], tokens
        whose choice the bias moved)``."""
        c = self.c
        w, idx, unbiased = self.route(h, p["mlp.router"], p["mlp.bias"])
        held = self.first + jnp.arange(self.held)
        gate = jnp.sum(w[..., None] * (idx[..., None] == held), axis=-2)
        hidden = jax.nn.silu(c("bsd,edf->bsef", h, p["mlp.wg"])) \
            * c("bsd,edf->bsef", h, p["mlp.wi"])
        y = c("bsef,efd->bsd", hidden * gate[..., None], p["mlp.wo"])
        y = y + self._swiglu(h, p["mlp.shared.wg"], p["mlp.shared.wi"],
                             p["mlp.shared.wo"])
        load = jnp.zeros((self.experts,), jnp.int32).at[idx.ravel()].add(1)
        moved = jnp.sum(jnp.any(jnp.sort(idx, -1) != jnp.sort(unbiased, -1),
                                axis=-1))
        return y, load, moved

    def _moe(self, x, p):
        x = self._attention(x, p)
        y, load, moved = self.moe_mlp(self._rms(x, p["norm2.scale"]), p)
        return x + y, (load, moved)

    def loss(self, params: Dict[str, jax.Array], tokens, labels):
        """``(mean next-token cross-entropy, (pairs choosing each expert
        per MoE layer [L, E], tokens whose choice the bias moved per MoE
        layer [L]))``."""
        x = params["embed.tok"][tokens]

        def dense(x, p):
            return jax.checkpoint(self._dense)(x, p), None

        def moe(x, p):
            return jax.checkpoint(self._moe)(x, p)

        if self.dense:
            x, _ = jax.lax.scan(dense, x, {n: params["dense." + n]
                                           for n in DENSE_NAMES})
        x, counts = jax.lax.scan(moe, x, {n: params["moe." + n]
                                          for n in MOE_NAMES})
        x = self._rms(x, params["final_norm.scale"])
        logits = self.c("bsd,dv->bsv", x, params["embed.head"])
        logz = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
        return jnp.mean(logz - gold), counts


@functools.lru_cache(maxsize=8)
def _grad_fn(model_json: str, rounding: Optional[Callable]):
    """The jitted loss and gradient of one model, kept for the process:
    runs of one cell share its compilation."""
    return jax.jit(jax.value_and_grad(Model(json.loads(model_json),
                                            rounding).loss, has_aux=True))


_STEP_FNS: Dict[str, tuple] = {}


class AdamW(qwen_ref.AdamW):
    """``qwen_ref.AdamW``'s step (f32 moments, global-norm clipping,
    decoupled weight decay; gradients summed over ``micro_batches``
    slices of the rows) on this model, whose gradient comes with the
    routing counts of the batch."""

    def __init__(self, model: dict, opt: dict, total_steps: int,
                 rounding: Optional[Callable] = None,
                 micro_batches: int = 1):
        super().__init__(model, opt, total_steps, rounding, micro_batches)
        self._grad = _grad_fn(json.dumps(model, sort_keys=True), rounding)
        # the update and the micro-batch sums depend on the optimizer
        # alone: every model and rounding shares their compilations
        self._update, self._add, self._times = _STEP_FNS.setdefault(
            json.dumps(opt, sort_keys=True),
            (self._update, self._add, self._times))

    def grad(self, p: Dict[str, jax.Array], tokens, labels):
        """``(mean loss as a float, gradient, (load [L, E], tokens moved
        by the bias [L]) summed over the rows)``."""
        n = tokens.shape[0] // self.micro
        loss, g, w, counts = 0.0, None, 1.0 / self.micro, None
        for i in range(self.micro):
            (li, ci), gi = self._grad(
                p, jnp.asarray(tokens[i * n:(i + 1) * n]),
                jnp.asarray(labels[i * n:(i + 1) * n]))
            loss += float(li) * w
            counts = ci if counts is None else jax.tree_util.tree_map(
                jnp.add, counts, ci)
            g = (gi if self.micro == 1 else self._times(gi, w)
                 if g is None else self._add(g, gi, w))
            del gi
        return loss, g, counts


@functools.lru_cache(maxsize=8)
def _adamw(model_json: str, opt_json: str, total_steps: int,
           micro_batches: int) -> AdamW:
    """One :class:`AdamW` per model and optimizer, kept for the process:
    runs of one cell share its compilations."""
    return AdamW(json.loads(model_json), json.loads(opt_json), total_steps,
                 micro_batches=micro_batches)


def train_readings(model: dict, opt: dict, params0: Dict[str, jax.Array],
                   batches: Sequence[dict], total_steps: int,
                   micro_batches: int = 1) -> dict:
    """``qwen_ref.train_readings``'s numbers (every step's loss, each
    parameter's norm of the first gradient before and after clipping,
    and of its change over the steps), and of the first batch the pairs
    choosing each expert per MoE layer (``load``, [L, E]) and the share
    of its tokens whose choice the bias moved (``bias_moved``, per MoE
    layer)."""
    ref = _adamw(json.dumps(model, sort_keys=True),
                 json.dumps(opt, sort_keys=True), total_steps, micro_batches)
    p, m, v = _start(params0)
    losses: List[float] = []
    out: dict = {}
    for t, batch in enumerate(batches, start=1):
        loss, g, (load, moved) = ref.grad(p, batch["tokens"],
                                          batch["labels"])
        losses.append(loss)
        if t == 1:
            out["raw_grad_norm"] = _floats(_norms(g))
            out["load"] = np.asarray(load)
            out["bias_moved"] = (np.asarray(moved)
                                 / np.asarray(batch["tokens"]).size).tolist()
        p, m, v, seen = ref.update(p, g, m, v, t)
        if t == 1:
            out["grad_norm"] = {k: float(x) for k, x in seen.items()}
        del g
    out["loss"] = losses
    out["change_norm"] = _floats(_change_norms(p, params0))
    return out


@jax.jit
def _start(params0):
    """f32 copies of the parameters (the update donates them) and two
    sets of zero moments."""
    p = {k: x.astype(jnp.float32) + 0.0 for k, x in params0.items()}
    return (p, {k: jnp.zeros(x.shape, jnp.float32) for k, x in p.items()},
            {k: jnp.zeros(x.shape, jnp.float32) for k, x in p.items()})


@jax.jit
def _norms(tree):
    return {k: jnp.linalg.norm(x.ravel()) for k, x in tree.items()}


@jax.jit
def _change_norms(p, params0):
    return {k: jnp.linalg.norm((p[k] - params0[k].astype(jnp.float32))
                               .ravel()) for k in p}


def _floats(tree) -> Dict[str, float]:
    return {k: float(x) for k, x in jax.device_get(tree).items()}
