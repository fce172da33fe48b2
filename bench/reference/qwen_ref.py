"""Plain reference of a dense Qwen1.5-style decoder and its first AdamW
training steps, in float32 with every contraction at ``HIGHEST``
precision. It imports nothing of the program.

The model: token embedding; per layer a pre-norm (RMSNorm) attention
block (q/k/v projections with bias, rotary positions over adjacent
channel pairs, causal softmax, output projection) and a pre-norm SwiGLU
MLP, each added to the residual; a final RMSNorm; logits against the
tied embedding; mean next-token cross-entropy. Parameters are a flat
dict of named arrays, layer weights stacked on a leading layer axis.

``rounding`` puts the nearest lower precision in its place, for the
control (``bench/faults.py``): each contraction's operands, and in the
backward pass its incoming gradient, are rounded to float8 (e4m3) with
one scale per tensor, as float8 training does.
"""

from __future__ import annotations

from typing import Callable, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
FP8_MAX = 448.0                      # largest finite float8_e4m3fn

LAYER_NAMES = ("norm1.scale", "mix.wq", "mix.bq", "mix.wk", "mix.bk",
               "mix.wv", "mix.bv", "mix.wo", "norm2.scale", "mlp.wg",
               "mlp.wi", "mlp.wo")


def round_fp8(x: jax.Array) -> jax.Array:
    """``x`` rounded to float8 e4m3 under one scale per tensor that maps
    its largest magnitude to the format's largest finite value."""
    amax = jax.lax.stop_gradient(jnp.max(jnp.abs(x)))
    s = jnp.where(amax > 0, FP8_MAX / amax, 1.0)
    return (x * s).astype(jnp.float8_e4m3fn).astype(jnp.float32) / s


def _einsum(spec: str, a, b):
    return jnp.einsum(spec, a, b, precision=HIGHEST)


def make_contract(rounding: Optional[Callable]) -> Callable:
    """``contract(spec, a, b)``: an einsum, exact or with ``rounding``
    applied to both operands and to the gradient flowing back."""
    if rounding is None:
        return _einsum

    def contract(spec, a, b):
        @jax.custom_vjp
        def f(a, b):
            return _einsum(spec, rounding(a), rounding(b))

        def fwd(a, b):
            ra, rb = rounding(a), rounding(b)
            return _einsum(spec, ra, rb), (ra, rb)

        def bwd(res, g):
            ra, rb = res
            _, vjp = jax.vjp(lambda x, y: _einsum(spec, x, y), ra, rb)
            return vjp(rounding(g))

        f.defvjp(fwd, bwd)
        return f(a, b)
    return contract


class Decoder:
    """The reference decoder for ``model`` (a configuration file's
    published keys)."""

    def __init__(self, model: dict, rounding: Optional[Callable] = None):
        self.d = int(model["hidden_size"])
        self.heads = int(model["num_attention_heads"])
        self.kv = int(model["num_key_value_heads"])
        self.hd = self.d // self.heads
        self.eps = float(model["rms_norm_eps"])
        self.theta = float(model["rope_theta"])
        self.c = make_contract(rounding)

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

    def _layer(self, x, p):
        c = self.c
        b, s, _ = x.shape
        h = self._rms(x, p["norm1.scale"])
        q = c("bsd,dh->bsh", h, p["mix.wq"]) + p["mix.bq"]
        k = c("bsd,dh->bsh", h, p["mix.wk"]) + p["mix.bk"]
        v = c("bsd,dh->bsh", h, p["mix.wv"]) + p["mix.bv"]
        q = self._rope(q.reshape(b, s, self.heads, self.hd))
        k = self._rope(k.reshape(b, s, self.kv, self.hd))
        v = v.reshape(b, s, self.kv, self.hd)
        g = self.heads // self.kv
        q = q.reshape(b, s, self.kv, g, self.hd)
        scores = c("bskgh,btkh->bkgst", q, k) / np.sqrt(self.hd)
        causal = jnp.tril(jnp.ones((s, s), bool))
        scores = jnp.where(causal, scores, -jnp.inf)
        probs = jax.nn.softmax(scores, axis=-1)
        o = c("bkgst,btkh->bskgh", probs, v).reshape(b, s, self.d)
        x = x + c("bsh,hd->bsd", o, p["mix.wo"])
        h = self._rms(x, p["norm2.scale"])
        gate = jax.nn.silu(c("bsd,df->bsf", h, p["mlp.wg"]))
        up = c("bsd,df->bsf", h, p["mlp.wi"])
        return x + c("bsf,fd->bsd", gate * up, p["mlp.wo"])

    def loss(self, params: Dict[str, jax.Array], tokens, labels):
        """Mean next-token cross-entropy of ``tokens`` against
        ``labels``."""
        x = params["embed.tok"][tokens]
        layers = {n: params["layers." + n] for n in LAYER_NAMES}

        def body(x, p):
            return jax.checkpoint(self._layer)(x, p), None

        x, _ = jax.lax.scan(body, x, layers)
        x = self._rms(x, params["final_norm.scale"])
        logits = self.c("bsd,vd->bsv", x, params["embed.tok"])
        logz = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
        return jnp.mean(logz - gold)


def lr_at(opt: dict, step: int, total_steps: int) -> float:
    """Linear warm-up to ``lr`` over ``warmup_steps``, then cosine decay
    to ``min_lr_frac`` of it at ``total_steps``."""
    lr, warm = float(opt["lr"]), int(opt["warmup_steps"])
    if step < warm:
        return lr * step / warm
    t = min(max((step - warm) / max(total_steps - warm, 1), 0.0), 1.0)
    lo = float(opt["min_lr_frac"]) * lr
    return lo + (lr - lo) * 0.5 * (1.0 + np.cos(np.pi * t))


def _norms(tree: Dict[str, jax.Array]) -> Dict[str, float]:
    return {k: float(jnp.linalg.norm(v.astype(jnp.float32).ravel()))
            for k, v in tree.items()}


class AdamW:
    """The reference's training step: the decoder's mean loss and its
    gradient, then AdamW (f32 moments, global-norm clipping, decoupled
    weight decay) on a flat dict of float32 parameters. Each batch's
    gradient is summed over ``micro_batches`` equal slices of its rows so
    the logits fit."""

    def __init__(self, model: dict, opt: dict, total_steps: int,
                 rounding: Optional[Callable] = None,
                 micro_batches: int = 1):
        self.opt, self.total_steps = opt, total_steps
        self.micro = micro_batches
        b1, b2 = float(opt["b1"]), float(opt["b2"])
        eps, wd = float(opt["eps"]), float(opt["weight_decay"])
        clip = float(opt["clip_norm"])
        self._grad = jax.jit(jax.value_and_grad(Decoder(model, rounding).loss))
        self._add = jax.jit(lambda a, b, w: jax.tree_util.tree_map(
            lambda x, y: x + w * y, a, b), donate_argnums=(0,))
        self._times = jax.jit(lambda a, w: jax.tree_util.tree_map(
            lambda x: x * w, a), donate_argnums=(0,))

        def update(p, g, m, v, t, lr):
            gnorm = jnp.sqrt(sum(jnp.sum(x * x) for x in g.values()))
            scale = jnp.minimum(1.0, clip / gnorm)
            out_p, out_m, out_v, seen = {}, {}, {}, {}
            for k in p:
                gk = g[k] * scale
                seen[k] = jnp.linalg.norm(gk.ravel())
                out_m[k] = b1 * m[k] + (1 - b1) * gk
                out_v[k] = b2 * v[k] + (1 - b2) * gk * gk
                mhat = out_m[k] / (1 - b1 ** t)
                vhat = out_v[k] / (1 - b2 ** t)
                out_p[k] = p[k] - lr * (mhat / (jnp.sqrt(vhat) + eps)
                                        + wd * p[k])
            return out_p, out_m, out_v, seen

        self._update = jax.jit(update, donate_argnums=(0, 1, 2, 3))

    def grad(self, p: Dict[str, jax.Array], tokens, labels):
        """``(mean loss as a float, gradient)`` of one batch."""
        n = tokens.shape[0] // self.micro
        loss, g, w = 0.0, None, 1.0 / self.micro
        for i in range(self.micro):
            li, gi = self._grad(p, jnp.asarray(tokens[i * n:(i + 1) * n]),
                                jnp.asarray(labels[i * n:(i + 1) * n]))
            loss += float(li) * w
            g = (gi if self.micro == 1 else self._times(gi, w)
                 if g is None else self._add(g, gi, w))
            del gi
        return loss, g

    def update(self, p, g, m, v, t: int):
        """Step ``t`` (from 1): ``(params, m, v, each parameter's norm of
        the gradient as the optimizer takes it, after clipping)``. It
        donates ``p``, ``g``, ``m`` and ``v``."""
        return self._update(p, g, m, v, float(t),
                            lr_at(self.opt, t, self.total_steps))


def train_readings(model: dict, opt: dict, params0: Dict[str, jax.Array],
                   batches: Sequence[dict], total_steps: int,
                   micro_batches: int = 1) -> dict:
    """Run :class:`AdamW` over ``batches`` from ``params0`` and return
    what the cell compares: every step's loss, each parameter's norm of
    the first gradient as the optimizer takes it (after clipping), each
    one's norm of the unclipped first gradient (which parameters count),
    and each one's norm of its change over all the steps."""
    ref = AdamW(model, opt, total_steps, micro_batches=micro_batches)
    # copies: the update donates its parameters, and params0 is kept
    p = {k: jnp.array(x, jnp.float32, copy=True) for k, x in params0.items()}
    m = {k: jnp.zeros_like(x) for k, x in p.items()}
    v = {k: jnp.zeros_like(x) for k, x in p.items()}
    losses: List[float] = []
    out: dict = {}
    for t, batch in enumerate(batches, start=1):
        loss, g = ref.grad(p, batch["tokens"], batch["labels"])
        losses.append(loss)
        if t == 1:
            out["raw_grad_norm"] = _norms(g)
        p, m, v, seen = ref.update(p, g, m, v, t)
        if t == 1:
            out["grad_norm"] = {k: float(x) for k, x in seen.items()}
        del g
    out["loss"] = losses
    out["change_norm"] = {k: float(jnp.linalg.norm(
        (p[k] - jnp.asarray(params0[k], jnp.float32)).ravel())) for k in p}
    return out
