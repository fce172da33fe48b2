"""jit'd public wrappers for the Pallas kernels.

``interpret=`` selects Pallas interpret mode (CPU validation). On TPU
hardware call with ``interpret=False``.
``use_pallas_default()`` is consulted by the model stack: XLA fallbacks
(the same math, from the oracles) are used for the 512-device dry-run,
because a TPU Mosaic kernel does not compile on the CPU backend. The new
resident-store wrappers (:func:`fused_join_digest`, :func:`scatter_join`,
:func:`chunk_digest_auto`) bake that dispatch in: ``interpret=None``
means "compiled Pallas on TPU, the jitted XLA oracle elsewhere" — the
oracle is the identical math in one fused XLA dispatch, so the CPU path
keeps the launch-count story honest without paying interpret mode's
per-grid-step simulation cost on the hot path.

Every wrapper also feeds :data:`counters` — process-wide accounting of
kernel launches and host↔device staging bytes. A numpy operand handed to
a launch models one host→device upload of its ``nbytes`` (on a real
accelerator that is exactly what happens; on the CPU backend it is the
same bytes crossing the staging boundary); a jax.Array operand counts
zero, which is what makes the device-resident store measurable: its
steady-state rounds launch O(1) kernels over arrays that never leave the
device. Benchmarks snapshot/diff the counters around each round.
"""

from __future__ import annotations

import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import ref
from .delta_join import ROW_TILE, padded_rows
from .delta_join import batched_delta_join as _batched_delta_join
from .delta_join import chunk_digest as _chunk_digest
from .delta_join import delta_join as _delta_join
from .delta_join import fused_join_digest as _fused_join_digest
from .delta_join import scatter_join as _scatter_join
from .flash_attention import flash_attention_fwd as _flash_fwd
from .flash_attention import flash_decode_fwd as _flash_decode


def use_pallas_default() -> bool:
    """Whether the Mosaic Pallas kernels compile on the current backend.
    On TPU call the kernels with ``interpret=False``; elsewhere use
    interpret mode / the XLA oracles."""
    return jax.default_backend() == "tpu"


# ---------------------------------------------------------------------------
# Launch / transfer accounting
# ---------------------------------------------------------------------------

class KernelCounters:
    """Process-wide kernel-launch and host↔device byte accounting.

    ``launches`` counts wrapper-level kernel dispatches (one fused
    pipeline == one launch, however many outputs it writes).
    ``h2d_bytes`` counts bytes staged host→device: the ``nbytes`` of
    every *numpy* operand handed to a launch (device-resident jax.Array
    operands cost nothing — that is the resident store's whole claim).
    ``d2h_bytes`` counts bytes explicitly pulled back to host
    (:meth:`count_d2h` — spills, ranking results). ``by_kernel`` splits
    the launches by ``"name:mode"`` (see :func:`record_launch`), so a run
    can show that its kernels ran compiled on the device.

    The counters are monotone for the process lifetime and are read by
    **snapshot-and-diff only** (:meth:`snapshot` / :meth:`since`): a
    global reset would race every other measurement window sharing the
    process — two ``GossipNode`` tick handlers interleaved on one event
    loop, a bench suite wrapping a cluster — silently corrupting
    whichever window the reset landed inside. Diffing two snapshots is
    interleaving-safe (each window sees exactly its own delta plus
    launches genuinely concurrent with it), so there deliberately is no
    ``reset()``; ``benchmarks/run.py --json`` records per-suite launch
    totals this way.
    """

    __slots__ = ("launches", "h2d_bytes", "d2h_bytes", "by_kernel")

    def __init__(self):
        self.launches = 0
        self.h2d_bytes = 0
        self.d2h_bytes = 0
        self.by_kernel: Dict[str, int] = {}   # "name:mode" → launches

    def snapshot(self) -> dict:
        return {"launches": self.launches, "h2d_bytes": self.h2d_bytes,
                "d2h_bytes": self.d2h_bytes}

    def since(self, snap: dict) -> dict:
        return {k: getattr(self, k) - v for k, v in snap.items()}

    def count_h2d(self, *arrays) -> None:
        """Record host→device staging for every numpy operand."""
        for a in arrays:
            if isinstance(a, np.ndarray):
                self.h2d_bytes += a.nbytes

    def count_d2h(self, *arrays) -> None:
        """Record an explicit device→host fetch of each array."""
        for a in arrays:
            nb = getattr(a, "nbytes", None)
            if nb is not None:
                self.d2h_bytes += int(nb)


counters = KernelCounters()


def record_launch(name: str, *operands, mode: str = "xla") -> None:
    """Account one named kernel dispatch in the counters. Every wrapper
    (and any out-of-module launch site, e.g. the resident store's ranking
    epilogue) routes through here so launches are counted by name, not
    just as a bare count. ``mode`` says what ran: ``"compiled"`` (a
    Mosaic kernel), ``"interpret"`` (Pallas interpret mode) or ``"xla"``
    (a jitted XLA program)."""
    counters.launches += 1
    key = f"{name}:{mode}"
    counters.by_kernel[key] = counters.by_kernel.get(key, 0) + 1
    counters.count_h2d(*operands)


def _mode(interpret: bool) -> str:
    return "interpret" if interpret else "compiled"


# ---------------------------------------------------------------------------
# Attention
# ---------------------------------------------------------------------------

_flash_attention_jit = functools.partial(
    jax.jit, static_argnames=("scale", "window", "softcap", "block_q",
                              "block_k", "interpret"))(_flash_fwd)
_flash_decode_jit = functools.partial(
    jax.jit, static_argnames=("scale", "window", "softcap", "block_k",
                              "interpret"))(_flash_decode)


def flash_attention(q, k, v, *, scale: Optional[float] = None,
                    window: Optional[int] = None,
                    softcap: Optional[float] = None,
                    block_q: int = 128, block_k: int = 128,
                    interpret: bool = False):
    """Causal flash attention. q [b,h,s,hd]; k,v [b,kv,s,hd]."""
    record_launch("flash_attention", q, k, v, mode=_mode(interpret))
    return _flash_attention_jit(q, k, v, scale=scale, window=window,
                                softcap=softcap, block_q=block_q,
                                block_k=block_k, interpret=interpret)


def flash_decode(q, k, v, q_pos, k_pos, *, scale: Optional[float] = None,
                 window: Optional[int] = None,
                 softcap: Optional[float] = None,
                 block_k: int = 128, interpret: bool = False):
    """One-token decode against a (ring) KV cache with slot positions."""
    record_launch("flash_decode", q, k, v, q_pos, k_pos,
                  mode=_mode(interpret))
    return _flash_decode_jit(q, k, v, q_pos, k_pos, scale=scale,
                             window=window, softcap=softcap,
                             block_k=block_k, interpret=interpret)


# ---------------------------------------------------------------------------
# δ-CRDT joins and digests
# ---------------------------------------------------------------------------

def _store_kernel(fn):
    """``fn`` traced under ``jax.named_scope("store.<name>")``, so its
    device operations carry the kernel's name in the profiler trace; the
    jitted module keeps ``fn``'s own name (``jit_<name>``)."""
    return jax.named_scope(f"store.{fn.__name__}")(fn)


_delta_join_jit = functools.partial(
    jax.jit, static_argnames=("block_n", "interpret"))(
        _store_kernel(_delta_join))


def delta_join(a_vals, a_vers, b_vals, b_vers, *, block_n: int = ROW_TILE,
               interpret: bool = False) -> Tuple[jax.Array, jax.Array]:
    """Fused versioned-chunk LWW merge (the δ-CRDT tensor join hot loop)."""
    record_launch("delta_join", a_vals, a_vers, b_vals, b_vers,
                  mode=_mode(interpret))
    return _delta_join_jit(a_vals, a_vers, b_vals, b_vers, block_n=block_n,
                           interpret=interpret)


def batched_delta_join(segments, *, block_n: int = ROW_TILE,
                       interpret: bool = False, host_stage: bool = False):
    """Stacked versioned-chunk merge over many objects' chunks: segments
    sharing a (chunk-width, dtype) signature run as ONE kernel launch
    (via the jit'd :func:`delta_join`, so repeated stacked shapes hit the
    dispatch cache). ``host_stage=True`` selects the numpy-staged CPU
    glue (single-grid-step launch, numpy-view outputs). Returns
    (out_vals, out_vers) per segment."""
    return _batched_delta_join(
        segments, block_n=block_n, interpret=interpret,
        host_stage=host_stage,
        join_fn=lambda av, avr, bv, bvr: delta_join(
            av, avr, bv, bvr, block_n=block_n, interpret=interpret),
        host_join_fn=lambda av, avr, bv, bvr, rows: delta_join(
            av, avr, bv, bvr, block_n=rows, interpret=interpret))


_chunk_digest_jit = functools.partial(
    jax.jit, static_argnames=("block_n", "interpret"))(
        _store_kernel(_chunk_digest))
_chunk_digest_ref_jit = jax.jit(_store_kernel(ref.chunk_digest_ref))


def chunk_digest(x, *, block_n: int = ROW_TILE,
                 interpret: bool = False) -> Tuple[jax.Array, jax.Array]:
    """Per-chunk (max|x|, Σx²) in one pass — delta-selection digests."""
    record_launch("chunk_digest", x, mode=_mode(interpret))
    return _chunk_digest_jit(x, block_n=block_n, interpret=interpret)


def chunk_digest_auto(x, *, block_n: int = ROW_TILE
                      ) -> Tuple[jax.Array, jax.Array]:
    """:func:`chunk_digest` on the best backend available: compiled
    Pallas on TPU, the jitted XLA oracle elsewhere (identical math, one
    fused dispatch either way). The digest-selection hot path calls this
    instead of paying interpret mode's per-grid-step simulation cost per
    tensor."""
    if use_pallas_default():
        return chunk_digest(x, block_n=block_n)
    record_launch("chunk_digest", x, mode="xla")
    return _chunk_digest_ref_jit(x)


_fused_join_digest_jit = functools.partial(
    jax.jit, static_argnames=("block_n", "interpret"))(
        _store_kernel(_fused_join_digest))
_fused_join_digest_ref_jit = jax.jit(
    _store_kernel(ref.fused_join_digest_ref))


def fused_join_digest(a_vals, a_vers, b_vals, b_vers, *,
                      block_n: int = ROW_TILE,
                      interpret: Optional[bool] = None
                      ) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Join + digest-of-the-merge in ONE launch: ``(out_vals, out_vers,
    max|out| per chunk, Σout² per chunk)``. ``interpret=None`` (default)
    auto-dispatches — compiled Pallas on TPU, the jitted XLA oracle
    elsewhere; pass True/False to force a Pallas mode (parity tests)."""
    operands = (a_vals, a_vers, b_vals, b_vers)
    if interpret is None and not use_pallas_default():
        record_launch("fused_join_digest", *operands, mode="xla")
        return _fused_join_digest_ref_jit(*operands)
    interpret = bool(interpret)
    record_launch("fused_join_digest", *operands, mode=_mode(interpret))
    return _fused_join_digest_jit(*operands, block_n=block_n,
                                  interpret=interpret)


_scatter_join_jit = functools.partial(
    jax.jit, static_argnames=("interpret",))(_store_kernel(_scatter_join))
_scatter_join_ref_jit = jax.jit(_store_kernel(ref.scatter_join_ref))


def scatter_join(vals, vers, maxabs, sumsq, idx, d_vals, d_vers, *,
                 interpret: Optional[bool] = None
                 ) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Scatter-merge sparse delta rows into resident stacked columns and
    refresh the touched rows' digest — the one-launch ingest behind
    ``kernels.resident``. ``interpret=None`` auto-dispatches like
    :func:`fused_join_digest`. ``idx`` empty is a no-op (no launch)."""
    if int(idx.shape[0]) == 0:
        return vals, vers, maxabs, sumsq
    operands = (vals, vers, maxabs, sumsq, idx, d_vals, d_vers)
    if interpret is None and not use_pallas_default():
        record_launch("scatter_join", *operands, mode="xla")
        return _scatter_join_ref_jit(*operands)
    interpret = bool(interpret)
    record_launch("scatter_join", *operands, mode=_mode(interpret))
    return _scatter_join_jit(*operands, interpret=interpret)


# re-export the oracles for convenience
attention_ref = ref.attention_ref
decode_ref = ref.decode_ref
delta_join_ref = ref.delta_join_ref
batched_delta_join_ref = ref.batched_delta_join_ref
chunk_digest_ref = ref.chunk_digest_ref
fused_join_digest_ref = ref.fused_join_digest_ref
scatter_join_ref = ref.scatter_join_ref
