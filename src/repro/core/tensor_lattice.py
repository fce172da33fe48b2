"""Join-semilattices over JAX tensors — the δ-CRDT ⇄ training-state bridge.

Two lattices carry the framework's replicated ML state:

1. ``TensorState`` — a *versioned chunk store*: every tensor is split into
   fixed-size chunks, each tagged with a totally-ordered version
   ``(lamport_counter, writer_rank)`` packed into one int64. The join keeps,
   per chunk, the value with the larger version (pointwise LWW) — a
   join-semilattice because versions are unique per write and the order is
   total. This is the δ-CRDT the checkpointing and parameter-replication
   layers gossip: a *delta* is a TensorState containing only touched
   tensors, and the wire format (``pack_delta``) additionally drops
   untouched chunks. The hot join path (`masked version merge`, one pass
   over HBM) is the ``kernels/delta_join`` Pallas kernel on TPU; the jnp
   fallback below is the oracle and the CPU path.

2. ``DotSumStore`` — a grow-only map dot → update-pytree with join = union
   (unique dots ⇒ no conflicts): the additive lattice used for cross-pod
   pseudo-gradient aggregation (local-SGD / DiLoCo-style outer updates).
   Its value is ``sum of all dots``; duplicates and reordering are absorbed
   by the union. ``IntervalSum`` is its §7.2-style compression: under
   causal delta-interval delivery (Algorithm 2), the explicit dot cloud
   collapses to (version-vector, running sum) — property-tested equivalent
   to the reference store.

All lattice values implement ``join``/``leq``/``==`` so the generic
anti-entropy nodes in ``repro.core.antientropy`` run unchanged over them.
"""

from __future__ import annotations

import dataclasses
import functools
from dataclasses import dataclass
from typing import Any, Callable, Dict, Iterable, Mapping, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..obs.registry import global_registry

# version = (lamport << RANK_BITS) | writer_rank, stored in a jnp integer
# array. Without jax_enable_x64 jnp canonicalizes int64 → int32, so keep the
# rank field small enough that lamport gets ≥ 2^21 headroom (≈ 2M writes per
# tensor-chunk lifetime; checkpoints reset clocks). 1024 writer ranks covers
# pod-level replication (replicas are pods, not chips — see DESIGN.md §2).
RANK_BITS = 10
_RANK_MASK = (1 << RANK_BITS) - 1

# jnp canonical integer dtype for version arrays (int32 unless x64 enabled).
VERSION_DTYPE = jnp.int64 if jax.config.jax_enable_x64 else jnp.int32


def make_version(lamport: int, rank: int) -> int:
    assert 0 <= rank < (1 << RANK_BITS)
    return (int(lamport) << RANK_BITS) | int(rank)


def version_lamport(v: int) -> int:
    return int(v) >> RANK_BITS


# ---------------------------------------------------------------------------
# Versioned chunk store
# ---------------------------------------------------------------------------

@dataclass(frozen=True, eq=False)
class ChunkedTensor:
    """One tensor as [n_chunks, chunk_size] values + [n_chunks] int64 versions.

    Version 0 == ⊥ for that chunk (values must be zeros there).
    """

    values: jax.Array    # [n_chunks, chunk_size]
    versions: jax.Array  # [n_chunks] int64

    is_sparse = False

    @property
    def shape(self) -> Tuple[int, int]:
        return tuple(self.values.shape)

    def __eq__(self, other: object) -> bool:
        if isinstance(other, SparseChunks):
            return _pair_eq(self, other)
        if not isinstance(other, ChunkedTensor):
            return NotImplemented
        return (self.values.shape == other.values.shape
                and bool(np.array_equal(np.asarray(self.versions),
                                        np.asarray(other.versions)))
                and bool(np.array_equal(np.asarray(self.values),
                                        np.asarray(other.values))))

    def __hash__(self):  # pragma: no cover
        raise TypeError("unhashable")


@dataclass(frozen=True, eq=False)
class SparseChunks:
    """Sparse chunk-row set: the wire-decoded form of a tensor delta.

    Holds only the shipped rows of a logically [n_chunks, chunk] versioned
    tensor — ``idx`` are the chunk positions (sorted, unique), ``vals`` /
    ``vers`` the corresponding rows; every unlisted chunk is ⊥. Decoded
    frames keep their rows as zero-copy views into the frame buffer, and
    joining a sparse delta into a dense resident tensor is a
    gather → LWW-merge → scatter over the listed rows only — O(shipped
    chunks), never a full-size zero-padded materialization.
    """

    n_chunks: int
    idx: np.ndarray    # [rows] chunk positions, sorted strictly increasing
    vals: np.ndarray   # [rows, chunk]
    vers: np.ndarray   # [rows]

    is_sparse = True

    @property
    def shape(self) -> Tuple[int, int]:
        return (self.n_chunks, int(self.vals.shape[1]))

    def to_dense(self) -> ChunkedTensor:
        """Materialize the full [n_chunks, chunk] tensor (⊥ elsewhere),
        cached — the fallback for dense-only consumers (digest ranking,
        unchunk, checkpointing); the join/leq/eq hot paths never call
        this. A decoded value can become durable resident state (a key
        the replica never writes locally is taken wholesale by the
        join), so dense accessors must work, not crash."""
        cached = self.__dict__.get("_dense_cache")
        if cached is None:
            vals = np.zeros((self.n_chunks, self.vals.shape[1]),
                            dtype=self.vals.dtype)
            vers = np.zeros((self.n_chunks,),
                            dtype=np.asarray(self.vers).dtype)
            if self.idx.size:
                vals[self.idx] = self.vals
                vers[self.idx] = self.vers
            cached = ChunkedTensor(vals, vers)
            object.__setattr__(self, "_dense_cache", cached)
        return cached

    @property
    def values(self):
        """Dense [n_chunks, chunk] view (lazily materialized) — lets
        dense-only consumers treat any chunk tensor uniformly."""
        return self.to_dense().values

    @property
    def versions(self):
        return self.to_dense().versions

    def __eq__(self, other: object) -> bool:
        if isinstance(other, (ChunkedTensor, SparseChunks)):
            return _pair_eq(self, other)
        return NotImplemented

    def __hash__(self):  # pragma: no cover
        raise TypeError("unhashable")


def sparse_chunks(n_chunks: int, idx, vals, vers) -> SparseChunks:
    """Construct a :class:`SparseChunks`, normalizing to sorted-unique
    row order (the codec emits sorted rows; ad-hoc callers may not).
    Duplicate chunk positions keep the highest-versioned row — LWW, the
    same rule the join applies."""
    idx = np.asarray(idx)
    vals = np.asarray(vals)
    vers = np.asarray(vers)
    if idx.size and not bool(np.all(idx[1:] > idx[:-1])):
        order = np.lexsort((vers, idx))     # by position, version asc
        idx, vals, vers = idx[order], vals[order], vers[order]
        last = np.r_[idx[1:] != idx[:-1], True]
        if not bool(last.all()):
            idx, vals, vers = idx[last], vals[last], vers[last]
    return SparseChunks(int(n_chunks), idx, vals, vers)


def _max_version(ct) -> int:
    """Largest version held by a dense or sparse chunk tensor (0 == ⊥)."""
    if ct.is_sparse:
        return int(np.max(np.asarray(ct.vers))) if ct.idx.size else 0
    return int(jnp.max(ct.versions)) if ct.versions.shape[0] else 0


def _join_dense_sparse(dense: ChunkedTensor,
                       sp: SparseChunks) -> ChunkedTensor:
    """Join a sparse delta into a dense tensor: gather the resident rows
    at the shipped positions, keep the higher-versioned side, scatter the
    winners back — O(shipped rows) work plus one buffer copy."""
    if sp.idx.size == 0:
        return dense
    dv = np.asarray(dense.values)
    dr = np.asarray(dense.versions)
    take = np.asarray(sp.vers) > dr[sp.idx]
    if not bool(take.any()):
        return dense
    rows = sp.idx[take]
    out_v = np.array(dv, copy=True)
    out_r = np.array(dr, copy=True)
    out_v[rows] = np.asarray(sp.vals)[take]
    out_r[rows] = np.asarray(sp.vers)[take]
    return ChunkedTensor(out_v, out_r)


def _join_sparse_sparse(a: SparseChunks, b: SparseChunks) -> SparseChunks:
    """Union of two sparse row sets; overlapping positions keep the higher
    version (ties carry identical values by unique-write construction)."""
    if a.idx.size == 0:
        return b
    if b.idx.size == 0:
        return a
    idx = np.concatenate([np.asarray(a.idx), np.asarray(b.idx)])
    vers = np.concatenate([np.asarray(a.vers), np.asarray(b.vers)])
    vals = np.concatenate([np.asarray(a.vals), np.asarray(b.vals)], axis=0)
    order = np.lexsort((vers, idx))          # by position, version ascending
    idx, vers, vals = idx[order], vers[order], vals[order]
    last = np.r_[idx[1:] != idx[:-1], True]  # max-version row per position
    return SparseChunks(a.n_chunks, idx[last], vals[last], vers[last])


def _values_dtype(ct):
    return ct.vals.dtype if ct.is_sparse else ct.values.dtype


def _pair_join(a, b):
    """Join two chunk tensors of any density mix. Both must hold the
    same ``[n_chunks, chunk]`` layout and value dtype: any other pair is
    a type error in every density mix (the dense path would broadcast,
    the sparse paths index out of range)."""
    if a.shape != b.shape or _values_dtype(a) != _values_dtype(b):
        raise ValueError(
            f"cannot join chunk tensors of layout {a.shape} "
            f"{_values_dtype(a)} and {b.shape} {_values_dtype(b)}")
    if not a.is_sparse and not b.is_sparse:
        v, vers = _join_chunked(a.values, a.versions, b.values, b.versions)
        return ChunkedTensor(v, vers)
    if a.is_sparse and b.is_sparse:
        return _join_sparse_sparse(a, b)
    return (_join_dense_sparse(b, a) if a.is_sparse
            else _join_dense_sparse(a, b))


def _pair_leq(a, b) -> bool:
    """Pointwise version order over any density mix (O(sparse rows))."""
    if not a.is_sparse and not b.is_sparse:
        return not bool(jnp.any(a.versions > b.versions))
    if a.is_sparse and not b.is_sparse:
        if a.idx.size == 0:
            return True
        return not bool(np.any(np.asarray(a.vers)
                               > np.asarray(b.versions)[a.idx]))
    if not a.is_sparse and b.is_sparse:
        av = np.asarray(a.versions)
        live_outside = av > 0
        if b.idx.size:
            live_outside = np.array(live_outside, copy=True)
            live_outside[b.idx] = False
            if bool(np.any(av[b.idx] > np.asarray(b.vers))):
                return False
        return not bool(live_outside.any())
    # sparse ≤ sparse: every live row of a must be covered by b
    live = np.asarray(a.vers) > 0
    ai, avr = a.idx[live], np.asarray(a.vers)[live]
    if ai.size == 0:
        return True
    if b.idx.size == 0:
        return False
    pos = np.searchsorted(np.asarray(b.idx), ai)
    pos_c = np.minimum(pos, b.idx.size - 1)
    found = (pos < b.idx.size) & (np.asarray(b.idx)[pos_c] == ai)
    if not bool(found.all()):
        return False
    return not bool(np.any(avr > np.asarray(b.vers)[pos_c]))


def _sp_live(sp: SparseChunks):
    live = np.asarray(sp.vers) > 0
    return sp.idx[live], np.asarray(sp.vals)[live], np.asarray(sp.vers)[live]


def live_rows(ct) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """(chunk positions, values rows, versions) of a chunk tensor's live
    chunks, sorted by position — directly from sparse row sets, by mask
    for dense. The shared row extractor behind the wire codec and the
    digest-diff machinery."""
    if ct.is_sparse:
        idx, vals, vers = _sp_live(ct)
        return np.asarray(idx, dtype=np.int32), vals, vers
    vers = np.asarray(ct.versions)
    mask = vers > 0
    idx = np.nonzero(mask)[0].astype(np.int32)
    return idx, np.asarray(ct.values)[idx], vers[idx]


def dense_versions(ct) -> np.ndarray:
    """The full [n_chunks] version column of a dense or sparse chunk
    tensor (version 0 == ⊥ at unlisted sparse positions) — what a digest
    summary carries per (key, tensor)."""
    if ct.is_sparse:
        vers = np.zeros(ct.n_chunks, dtype=np.asarray(ct.vers).dtype)
        if ct.idx.size:
            vers[ct.idx] = ct.vers
        return vers
    return np.asarray(ct.versions)


def _pair_eq(a, b) -> bool:
    """Value equality over any density mix. Relies on the ⊥ invariant
    (version 0 ⇒ zero values), which every constructor maintains."""
    if a.shape != b.shape:
        return False
    if not a.is_sparse and not b.is_sparse:
        return a == b
    if a.is_sparse and b.is_sparse:
        ai, av, ar = _sp_live(a)
        bi, bv, br = _sp_live(b)
        return (np.array_equal(ai, bi) and np.array_equal(ar, br)
                and np.array_equal(av, bv))
    dense, sp = (b, a) if a.is_sparse else (a, b)
    dv, dr = np.asarray(dense.values), np.asarray(dense.versions)
    si, sv, sr = _sp_live(sp)
    dense_vers = np.zeros_like(dr)
    dense_vers[si] = sr
    if not np.array_equal(dr, dense_vers):
        return False
    if si.size and not np.array_equal(dv[si], sv):
        return False
    # unlisted rows are ⊥ on both sides (invariant: version 0 ⇒ zeros)
    return True


def _join_chunked_impl(av, avers, bv, bvers):
    """Pointwise LWW merge — the jnp oracle for kernels/delta_join."""
    take_b = bvers > avers
    out_v = jnp.where(take_b[:, None], bv, av)
    out_vers = jnp.maximum(avers, bvers)
    return out_v, out_vers


_join_chunked = jax.jit(_join_chunked_impl)


def chunk_tensor(x: np.ndarray, chunk_size: int,
                 version: int = 0) -> ChunkedTensor:
    flat = np.asarray(x).reshape(-1)
    pad = (-len(flat)) % chunk_size
    if pad:
        flat = np.concatenate([flat, np.zeros(pad, flat.dtype)])
    vals = jnp.asarray(flat.reshape(-1, chunk_size))
    vers = jnp.full((vals.shape[0],), version, dtype=VERSION_DTYPE)
    return ChunkedTensor(vals, vers)


def unchunk(ct: ChunkedTensor, shape: Tuple[int, ...],
            dtype=None) -> jax.Array:
    n = int(np.prod(shape))
    flat = ct.values.reshape(-1)[:n]
    out = flat.reshape(shape)
    return out.astype(dtype) if dtype is not None else out


@dataclass(frozen=True, eq=False)
class TensorState:
    """The replicated-state lattice: name → ChunkedTensor (+ lamport clock).

    ``lamport`` is replica-local bookkeeping used to mint fresh versions; it
    rides along monotonically (max on join) and does not affect equality of
    the CRDT payload semantics (two replicas holding identical chunk data
    are converged regardless of their clocks — but we advance clocks on
    join so new writes always supersede everything observed).
    """

    chunks: Tuple[Tuple[str, ChunkedTensor], ...] = ()
    lamport: int = 0

    @staticmethod
    def bottom() -> "TensorState":
        return TensorState()

    @staticmethod
    def of(mapping: Mapping[str, ChunkedTensor], lamport: int = 0) -> "TensorState":
        return TensorState(tuple(sorted(mapping.items())), lamport)

    def as_dict(self) -> Dict[str, ChunkedTensor]:
        return dict(self.chunks)

    # -- lattice ----------------------------------------------------------------
    def join(self, other: "TensorState") -> "TensorState":
        a, b = self.as_dict(), other.as_dict()
        out: Dict[str, Any] = {}
        for k in set(a) | set(b):
            if k not in a:
                out[k] = b[k]
            elif k not in b:
                out[k] = a[k]
            else:
                out[k] = _pair_join(a[k], b[k])
        return TensorState.of(out, max(self.lamport, other.lamport))

    def leq(self, other: "TensorState") -> bool:
        a, b = self.as_dict(), other.as_dict()
        for k, ct in a.items():
            if k not in b:
                if _max_version(ct) > 0:
                    return False
                continue
            if not _pair_leq(ct, b[k]):
                return False
            # equal versions ⇒ equal values by construction (unique writes)
        return True

    def __eq__(self, other: object) -> bool:
        if not isinstance(other, TensorState):
            return NotImplemented
        a, b = self.as_dict(), other.as_dict()
        keys = set(a) | set(b)
        for k in keys:
            if k not in a or k not in b:
                # missing key is equal to an all-⊥ tensor of the same shape
                present = a.get(k, b.get(k))
                if _max_version(present) > 0:
                    return False
                continue
            if not _pair_eq(a[k], b[k]):
                return False
        return True

    def __hash__(self):  # pragma: no cover
        raise TypeError("unhashable")

    # -- delta-mutator -----------------------------------------------------------
    def write_delta(self, rank: int, name: str, new_values: Any,
                    chunk_idx: Optional[np.ndarray] = None,
                    chunk_size: Optional[int] = None) -> "TensorState":
        """δ-mutator: (re)write tensor ``name`` (or a subset of its chunks).

        Returns a delta containing ONLY the touched tensor, with touched
        chunks carrying a fresh version and untouched chunks at ⊥
        (version 0, zero values) — `X ⊔ delta` applies the write.
        """
        lam = self.lamport + 1
        ver = make_version(lam, rank)
        cur = self.as_dict().get(name)
        if cur is None:
            assert chunk_idx is None, "cannot partially write unknown tensor"
            assert chunk_size is not None
            ct = chunk_tensor(np.asarray(new_values), chunk_size, version=0)
            vals, vers = ct.values, jnp.full((ct.values.shape[0],), ver,
                                             dtype=VERSION_DTYPE)
            delta_ct = ChunkedTensor(vals, vers)
        else:
            if cur.is_sparse:   # writes need the dense addressing space
                cur = cur.to_dense()
            n_chunks, csz = cur.values.shape
            if chunk_idx is None:
                ct = chunk_tensor(np.asarray(new_values), csz)
                assert ct.values.shape == cur.values.shape
                delta_ct = ChunkedTensor(
                    ct.values, jnp.full((n_chunks,), ver, dtype=VERSION_DTYPE))
            else:
                idx = jnp.asarray(chunk_idx, dtype=jnp.int32)
                new_vals = jnp.asarray(new_values).reshape(len(chunk_idx), csz)
                vals = jnp.zeros_like(cur.values).at[idx].set(new_vals)
                vers = jnp.zeros((n_chunks,), dtype=VERSION_DTYPE).at[idx].set(ver)
                delta_ct = ChunkedTensor(vals, vers)
        return TensorState.of({name: delta_ct}, lamport=lam)

    def write_full(self, rank: int, name: str, new_values: Any,
                   chunk_idx: Optional[np.ndarray] = None,
                   chunk_size: Optional[int] = None) -> "TensorState":
        return self.join(self.write_delta(rank, name, new_values, chunk_idx,
                                          chunk_size))

    def decompose(self) -> list:
        """Per-tensor atoms (coarse join-decomposition) — lets the
        RemoveRedundant shipping policy drop tensors the receiver provably
        holds. Chunk-level trimming stays in ``pack_delta`` /
        ``digest_select`` (dense masks there, not one value per chunk)."""
        return [TensorState.of({name: ct}, lamport=self.lamport)
                for name, ct in self.chunks]


# -- digest-driven chunk selection --------------------------------------------

def chunk_digest_cached(ct) -> Tuple[np.ndarray, np.ndarray]:
    """Per-chunk (max|x|, Σx²) of a chunk tensor, memoized on the
    (immutable) tensor object. Joins reuse untouched keys' ``ct``
    objects, so across anti-entropy rounds only tensors that actually
    changed recompute their digest — the rest hit this cache. Sparse
    tensors memoize on their cached dense form. Runs via
    ``ops.chunk_digest_auto`` (compiled Pallas on TPU, the jitted XLA
    oracle elsewhere — identical math)."""
    from ..kernels import ops

    if ct.is_sparse:            # the digest ranks dense chunk positions
        ct = ct.to_dense()
    cached = ct.__dict__.get("_digest_cache")
    if cached is None:
        ma, ss = ops.chunk_digest_auto(ct.values)
        cached = (np.asarray(ma), np.asarray(ss))
        object.__setattr__(ct, "_digest_cache", cached)
    return cached


def digest_keep_plan(tensors, budget_bytes: int, interpret: bool = True):
    """The shared energy-ranked greedy selection behind ``digest_select``
    and ``store.digest_select_store``.

    ``tensors`` is an iterable of ``(scope, name, ChunkedTensor)`` (scope
    is the store key, or None for a single object). Per tensor,
    :func:`chunk_digest_cached` computes (max|x|, Σx²) per chunk in one
    pass over HBM — memoized per tensor object, so untouched keys never
    recompute; live chunks are ranked globally by Σx² (energy) and
    taken greedily until ``budget_bytes`` of chunk payload is spent.
    Chunks already at ⊥ never count against the budget. Returns None when
    everything fits, else ``{(scope, name): [kept chunk indices]}``.
    ``interpret`` is kept for API compatibility; the digest now always
    runs one fused dispatch per tensor (Pallas on TPU, the XLA oracle
    elsewhere — ``interpret=True``'s per-grid-step simulation added cost
    without changing a single output bit).
    """
    del interpret
    candidates = []   # (neg_energy, scope, name, chunk_idx, chunk_bytes)
    for scope, name, ct in tensors:
        if ct.is_sparse:        # the digest ranks dense chunk positions
            ct = ct.to_dense()
        vers = np.asarray(ct.versions)
        live = vers > 0
        if not live.any():
            continue
        _, sumsq = chunk_digest_cached(ct)
        per_chunk = (ct.values.dtype.itemsize * ct.values.shape[1]
                     + np.dtype(np.int64).itemsize + np.dtype(np.int32).itemsize)
        for i in np.nonzero(live)[0]:
            candidates.append((-float(sumsq[i]), scope, name, int(i),
                               per_chunk))

    if sum(c[4] for c in candidates) <= budget_bytes:
        return None

    keep: Dict[Tuple[Any, str], list] = {}
    spent = 0
    for neg_e, scope, name, i, nbytes in sorted(candidates):
        if spent + nbytes > budget_bytes:
            continue
        spent += nbytes
        keep.setdefault((scope, name), []).append(i)
    return keep


def mask_kept_chunks(ct, idx) -> ChunkedTensor:
    """Drop every chunk not in ``idx`` to ⊥ (version 0, zero values), so
    the result is ≤ the input in the lattice order and always safe to
    join."""
    if ct.is_sparse:
        ct = ct.to_dense()
    mask = np.zeros((ct.values.shape[0],), dtype=bool)
    mask[np.asarray(idx)] = True
    m = jnp.asarray(mask)
    vals = jnp.where(m[:, None], ct.values, jnp.zeros_like(ct.values))
    vers = jnp.where(m, ct.versions, jnp.zeros_like(ct.versions))
    return ChunkedTensor(vals, vers)


def digest_select(state: TensorState, budget_bytes: int,
                  interpret: bool = True) -> TensorState:
    """Keep only the top-magnitude chunks of ``state`` under a byte budget
    (see :func:`digest_keep_plan`) — the ``DigestBudget`` shipping
    policy's payload transform for single objects. If everything fits the
    input is returned unchanged."""
    tensors = state.as_dict()
    keep = digest_keep_plan(((None, name, ct) for name, ct in
                             tensors.items()), budget_bytes, interpret)
    if keep is None:
        return state
    out = {name: mask_kept_chunks(ct, keep[(None, name)])
           for name, ct in tensors.items() if keep.get((None, name))}
    return TensorState.of(out, lamport=state.lamport)


# -- wire format --------------------------------------------------------------

def pack_delta(delta: TensorState,
               known_versions: Optional[Mapping[str, np.ndarray]] = None
               ) -> Dict[str, Any]:
    """Sparse wire encoding: per tensor, only chunks with version above ⊥
    (and above the receiver's known version when supplied). This is the
    §4.1 ``size(mᵟ(X)) ≪ size(X)`` payload."""
    out: Dict[str, Any] = {"lamport": delta.lamport, "tensors": {}}
    for name, ct in delta.chunks:
        if ct.is_sparse:
            row_idx, vals, vers = _sp_live(ct)
            shape = ct.shape
            if known_versions and name in known_versions:
                keep = vers > np.asarray(known_versions[name])[row_idx]
                row_idx, vals, vers = row_idx[keep], vals[keep], vers[keep]
            if len(row_idx) == 0:
                continue
            out["tensors"][name] = (np.asarray(row_idx, dtype=np.int32),
                                    vals, vers, shape)
            continue
        vers = np.asarray(ct.versions)
        mask = vers > 0
        if known_versions and name in known_versions:
            mask &= vers > np.asarray(known_versions[name])
        idx = np.nonzero(mask)[0]
        if len(idx) == 0:
            continue
        out["tensors"][name] = (
            idx.astype(np.int32),
            np.asarray(ct.values)[idx],
            vers[idx],
            ct.values.shape,
        )
    return out


def unpack_delta(wire: Dict[str, Any], *, sparse: bool = True) -> TensorState:
    """Decode a :func:`pack_delta` message.

    ``sparse=True`` (default) keeps each tensor as a :class:`SparseChunks`
    row set — joining it into resident state is a gather/merge/scatter
    over the shipped rows only, so ingest costs O(shipped chunks).
    ``sparse=False`` restores the legacy behavior of materializing
    full-size zero-padded tensors (kept for dense-only consumers)."""
    chunks: Dict[str, Any] = {}
    for name, (idx, vals, vers, shape) in wire["tensors"].items():
        if sparse:
            chunks[name] = sparse_chunks(shape[0], idx, vals, vers)
            continue
        dense_v = np.zeros(shape, dtype=vals.dtype)
        dense_ver = np.zeros((shape[0],), dtype=np.int64)
        dense_v[idx] = vals
        dense_ver[idx] = vers
        chunks[name] = ChunkedTensor(jnp.asarray(dense_v),
                                     jnp.asarray(dense_ver))
    return TensorState.of(chunks, lamport=wire["lamport"])


def packed_size_bytes(wire: Dict[str, Any]) -> int:
    total = 8
    for name, (idx, vals, vers, _shape) in wire["tensors"].items():
        total += len(name) + idx.nbytes + vals.nbytes + vers.nbytes
    return total


# ---------------------------------------------------------------------------
# Additive dot-store (pseudo-gradient aggregation) + §7.2-style compression
# ---------------------------------------------------------------------------

def _count_eq_bytes(where: str, pairs) -> None:
    """Both sides' bytes of the array leaves compared ``where``."""
    global_registry().counter(
        "repro_dotstore_eq_bytes_total", "payload bytes DotSumStore.__eq__ compared, by where",
        ("where",)).labels(where).inc(
            sum(getattr(x, "nbytes", 0) for pair in pairs for x in pair))


@functools.lru_cache(maxsize=None)
def _device_holds(dtype) -> bool:
    """Whether a device array can have ``dtype`` exactly: no float64 or
    int64 without x64, no strings, objects or foreign byte order."""
    try:
        return (jax.dtypes.canonicalize_dtype(dtype) == dtype
                and jnp.result_type(dtype) == dtype)
    except TypeError:
        return False


def _on_device(x, y) -> bool:
    """Whether ``np.array_equal(x, y)`` can be computed on a device: one
    side is a ``jax.Array``, the other has a dtype, and both dtypes and the
    one numpy promotes the pair to are held exactly there."""
    if not (isinstance(x, jax.Array) or isinstance(y, jax.Array)):
        return False
    try:
        dts = (x.dtype, y.dtype, np.result_type(x.dtype, y.dtype))
    except (AttributeError, TypeError):
        return False
    return all(_device_holds(d) for d in dts)


@jax.jit
def _all_array_equal(xs, ys):
    """One device bool: every pair equal elementwise in the dtype numpy
    promotes it to (so NaN is unequal to itself). Compiled once per
    structure, shapes and dtypes of the pairs."""
    ok = jnp.bool_(True)
    for x, y in zip(xs, ys):
        dt = np.result_type(x.dtype, y.dtype)
        ok = ok & jnp.all(x.astype(dt) == y.astype(dt))
    return ok


def _payloads_equal(pairs) -> bool:
    """``all(np.array_equal(x, y) for x, y in pairs)``, with every pair
    that involves a ``jax.Array`` compared on its device and the answer
    read back as one bool (one ``jax.device_get``). Pairs of numpy leaves,
    and pairs whose dtypes the device cannot hold, stay on the host.
    The payloads of one call share a set of devices."""
    host, dev = [], []
    for x, y in pairs:
        if _on_device(x, y):
            if x.shape != y.shape:
                return False
            dev.append((x, y))
        else:
            host.append((x, y))
    for x, y in host:
        _count_eq_bytes("host", [(x, y)])
        if not np.array_equal(x, y):
            return False
    if not dev:
        return True
    _count_eq_bytes("device", dev)
    xs, ys = zip(*dev)
    return bool(jax.device_get(_all_array_equal(list(xs), list(ys))))


@dataclass(frozen=True, eq=False)
class DotSumStore:
    """Grow-only map (producer, seq) → update pytree; join = union.

    The lattice of cross-pod additive updates. ``total()`` — the quantity
    the optimizer consumes — is the sum over all dots; because the store
    is a *set* of uniquely-tagged contributions, duplicated or reordered
    delivery cannot double-count (the paper's counter argument, §4.2).
    """

    dots: Tuple[Tuple[Tuple[str, int], Any], ...] = ()

    @staticmethod
    def bottom() -> "DotSumStore":
        return DotSumStore()

    def as_dict(self) -> Dict[Tuple[str, int], Any]:
        return dict(self.dots)

    def contribute_delta(self, producer: str, update: Any) -> "DotSumStore":
        """δ-mutator: a fresh uniquely-dotted contribution."""
        seq = 1 + max((s for (p, s), _ in self.dots if p == producer),
                      default=0)
        return DotSumStore((((producer, seq), update),))

    def contribute_full(self, producer: str, update: Any) -> "DotSumStore":
        return self.join(self.contribute_delta(producer, update))

    def join(self, other: "DotSumStore") -> "DotSumStore":
        merged = self.as_dict()
        for dot, upd in other.dots:
            if dot in merged:
                continue  # unique dots ⇒ identical payload
            merged[dot] = upd
        return DotSumStore(tuple(sorted(merged.items(),
                                        key=lambda kv: kv[0])))

    def decompose(self) -> list:
        """One atom per dot — RemoveRedundant trims re-gossiped dots the
        receiver has already acked."""
        return [DotSumStore((entry,)) for entry in self.dots]

    def leq(self, other: "DotSumStore") -> bool:
        od = other.as_dict()
        return all(dot in od for dot, _ in self.dots)

    def __eq__(self, other: object) -> bool:
        if not isinstance(other, DotSumStore):
            return NotImplemented
        a, b = self.as_dict(), other.as_dict()
        if set(a) != set(b):
            return False
        pairs = []
        for k in a:
            la, ta = jax.tree_util.tree_flatten(a[k])
            lb, tb = jax.tree_util.tree_flatten(b[k])
            if ta != tb or len(la) != len(lb):
                return False
            pairs.extend(zip(la, lb))
        return _payloads_equal(pairs)

    def __hash__(self):  # pragma: no cover
        raise TypeError("unhashable")

    def total(self) -> Any:
        if not self.dots:
            return None
        acc = jax.tree_util.tree_map(lambda x: jnp.asarray(x),
                                     self.dots[0][1])
        for _, upd in self.dots[1:]:
            acc = jax.tree_util.tree_map(lambda a, b: a + jnp.asarray(b),
                                         acc, upd)
        return acc

    def version_vector(self) -> Dict[str, int]:
        vv: Dict[str, int] = {}
        for (p, s), _ in self.dots:
            vv[p] = max(vv.get(p, 0), s)
        return vv


class IntervalSum:
    """§7.2-compressed DotSumStore: (per-producer contiguous prefix, sum).

    NOT a free-standing semilattice — the sum cannot deduplicate arbitrary
    overlaps — but under Algorithm-2 delivery (delta-intervals aligned with
    the receiver's acked prefix: the causal delta-merging condition) it is
    an exact, O(1)-memory encoding of the dot store. ``apply_interval``
    enforces the condition and is idempotent for re-delivered intervals.
    """

    def __init__(self):
        self.prefix: Dict[str, int] = {}
        self.sum: Any = None

    def apply_interval(self, producer: str, start_seq: int,
                       updates: Iterable[Any]) -> bool:
        """Apply contributions ``start_seq .. start_seq+len-1`` from
        ``producer``. Returns True if applied; False if rejected (gap —
        the merging condition X ⊒ Xʲᵃ does not hold) or fully stale."""
        updates = list(updates)
        have = self.prefix.get(producer, 0)
        if start_seq - 1 > have:
            return False                      # gap: would skip dots
        end = start_seq + len(updates) - 1
        if end <= have:
            return True                       # duplicate: already absorbed
        fresh = updates[have - (start_seq - 1):]  # drop already-applied prefix
        for upd in fresh:
            if self.sum is None:
                self.sum = jax.tree_util.tree_map(
                    lambda x: jnp.asarray(x).copy(), upd)
            else:
                self.sum = jax.tree_util.tree_map(
                    lambda a, b: a + jnp.asarray(b), self.sum, upd)
        self.prefix[producer] = end
        return True

    def matches(self, ref: DotSumStore, atol: float = 1e-6) -> bool:
        """Exactness check against the reference dot store."""
        if ref.version_vector() != {p: n for p, n in self.prefix.items()
                                    if n > 0}:
            return False
        t = ref.total()
        if t is None or self.sum is None:
            return t is None and self.sum is None
        la = jax.tree_util.tree_leaves(t)
        lb = jax.tree_util.tree_leaves(self.sum)
        return all(np.allclose(np.asarray(a), np.asarray(b), atol=atol)
                   for a, b in zip(la, lb))
