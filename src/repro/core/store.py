"""Keyed δ-CRDT object store: a map of independent lattice objects that is
itself a join-semilattice.

The paper's anti-entropy algorithms replicate *one* object per replica; a
serving fleet replicates *millions* (one session table per request, one
tensor shard per model slice, one membership view…). ``LatticeStore`` lifts
any family of lattices to a keyed store with the **pointwise** order:

* join  — per key: both sides present ⇒ ``a[k].join(b[k])``; one side ⇒
          that value (the other side is implicitly at that key's ⊥);
* ⊥     — the empty store; a key bound to its own type's bottom is
          indistinguishable from an absent key (``leq``/``==`` treat them
          identically), so deltas stay sparse;
* δ     — a store containing only the touched keys, each holding a delta
          of the embedded type. Joining single-key deltas yields multi-key
          store deltas, which is how per-key delta-intervals aggregate
          into one store-level wire message in the propagation engine.

This is a semilattice because the product of semilattices under the
pointwise order is one; heterogeneous value types are fine as long as each
*key* keeps one type across its lifetime (joining a GCounter into an
AWORSet at the same key is a type error, exactly as it would be without
the store).

The join has a **batched fast path**: when both sides hold
``tensor_lattice.TensorState`` values under many keys, the per-chunk LWW
merges are stacked into one ``kernels.delta_join`` Pallas launch
(``kernels.ops.batched_delta_join``) instead of one jit dispatch per key —
the objects/sec win measured by ``benchmarks/bench_store.py``. The
per-key Python loop remains as the fallback (``batched=False``, or
automatically for keys whose tensors cannot be stacked).

**Key lifecycle** (``repro.lifecycle``): alongside each value the store
carries a per-key :data:`~repro.lifecycle.lattice.Life` ``(epoch,
expiry)`` — the lexicographic lifecycle lattice. The per-key state is the
lex product ``Life ×lex Value``: equal epochs join expiries (max) and
values (pointwise) as ever; a higher epoch wins wholesale, so a compact
*tombstone* (bumped epoch, no value) ⊥-absorbs every straggler delta
from the reaped incarnation. Keys never touched by the lifecycle
subsystem sit at ``LIFE_BOTTOM`` (canonically absent from ``life``), so
plain stores behave exactly as before.

Replica integration lives in :mod:`repro.core.propagation`: ``Replica``'s
durable state is a ``LatticeStore`` (single-object replicas are one-key
stores behind a view property), and ``StoreReplica`` exposes the keyed
API. Hash-sharded key ownership is :mod:`repro.sync.membership`
(``KeyOwnership`` / ``ShardByKey``); the expiry/reaper machinery is
:mod:`repro.lifecycle`.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Callable, Dict, FrozenSet, Iterable, List, Mapping, Tuple

from ..lifecycle.lattice import LIFE_BOTTOM, Life, life_join
from ..obs.trace import span


def _is_bottom(value: Any) -> bool:
    """A value equal to its own type's bottom is lattice-identity."""
    return value == type(value).bottom()


@dataclass(frozen=True, eq=False)
class LatticeStore:
    """key → lattice value, itself a join-semilattice (pointwise order).

    ``life`` is the per-key lifecycle component (epoch, expiry) — see the
    module docstring; an entry's value lives *at* its key's life epoch.
    ``LIFE_BOTTOM`` entries are canonically absent.
    """

    entries: Tuple[Tuple[str, Any], ...] = ()
    life: Tuple[Tuple[str, Life], ...] = ()

    # -- construction -----------------------------------------------------------
    @staticmethod
    def bottom() -> "LatticeStore":
        return LatticeStore()

    @staticmethod
    def of(mapping: Mapping[str, Any],
           life: Mapping[str, Life] = ()) -> "LatticeStore":
        return LatticeStore(tuple(sorted(mapping.items())),
                            _canon_life(dict(life).items()))

    @staticmethod
    def key_delta(key: str, delta_value: Any) -> "LatticeStore":
        """δ-mutator lift: a store delta touching exactly one key."""
        return LatticeStore(((key, delta_value),))

    @staticmethod
    def life_delta(key: str, life: Life) -> "LatticeStore":
        """A store delta carrying only lifecycle state for ``key`` — a
        touch (expiry extension) or, with a bumped epoch, a tombstone."""
        return LatticeStore((), _canon_life([(key, life)]))

    def with_life(self, key: str, life: Life) -> "LatticeStore":
        """This store with ``life`` joined into ``key``'s lifecycle —
        how a write delta is stamped with the epoch/TTL it targets."""
        m = dict(self.life)
        m[key] = life_join(m.get(key, LIFE_BOTTOM), life)
        return LatticeStore(self.entries, _canon_life(m.items()))

    # -- views ------------------------------------------------------------------
    def as_dict(self) -> Dict[str, Any]:
        return dict(self.entries)

    def keys(self) -> FrozenSet[str]:
        return frozenset(k for k, _ in self.entries)

    def all_keys(self) -> FrozenSet[str]:
        """Keys with *any* state — a value, an expiry, or a tombstone.
        Sharding/handoff/reaping must iterate this, not ``keys()``:
        tombstones carry no value but must still route and replicate."""
        return self.keys() | frozenset(k for k, _ in self.life)

    def life_of(self, key: str) -> Life:
        return dict(self.life).get(key, LIFE_BOTTOM)

    def tombstoned(self, key: str) -> bool:
        """Reaped and not revived: a past-0 epoch holding no value."""
        return self.life_of(key)[0] > 0 and key not in self.as_dict()

    def tombstoned_keys(self) -> FrozenSet[str]:
        """All tombstoned keys in ONE pass — polling loops ("is the
        whole fleet reaped yet?") should use this instead of calling
        :meth:`tombstoned` per key, which rebuilds both dicts each
        call."""
        held = {k for k, _ in self.entries}
        return frozenset(k for k, (epoch, _) in self.life
                         if epoch > 0 and k not in held)

    def get(self, key: str, typ=None):
        """Value at ``key``; ``typ.bottom()`` (or None) when absent."""
        val = self.as_dict().get(key)
        if val is None and typ is not None:
            return typ.bottom()
        return val

    def restrict(self, keys: Iterable[str]) -> "LatticeStore":
        """Sub-store of the given keys (the ownership-sharding projection).
        Always ≤ self, so joining a restriction is always safe. Carries
        the kept keys' lifecycle state too — tombstones shard and hand
        off like values."""
        keep = set(keys)
        return LatticeStore(tuple((k, v) for k, v in self.entries
                                  if k in keep),
                            tuple((k, lv) for k, lv in self.life
                                  if k in keep))

    # -- δ-mutator lift ----------------------------------------------------------
    def apply_delta(self, key: str, typ, mutator_name: str,
                    *args) -> "LatticeStore":
        """Lift a δ-mutator of the embedded type at ``key``: the returned
        store delta contains only that key. Mirrors ``ORMap.apply_delta``
        (args include the replica id when the mutator wants one)."""
        cur = self.get(key, typ)
        sub_delta = getattr(cur, mutator_name)(*args)
        return LatticeStore.key_delta(key, sub_delta)

    def update_delta(self, key: str, typ,
                     fn: Callable[[Any], Any]) -> "LatticeStore":
        """Like ``apply_delta`` with a free-form mutator function."""
        return LatticeStore.key_delta(key, fn(self.get(key, typ)))

    # -- lattice ----------------------------------------------------------------
    def _epochs(self) -> Dict[str, int]:
        """key → nonzero life epoch (absent ⇒ 0) — the part of the
        lifecycle that decides which side's value contributes to a join."""
        return {k: lv[0] for k, lv in self.life if lv[0]}

    def join(self, other: "LatticeStore", *,
             batched: bool = True) -> "LatticeStore":
        if self.__dict__.get("_resident_cache") is None:
            return self._join(other, batched)
        with span("resident.join"):
            return self._join(other, batched)

    def _join(self, other: "LatticeStore",
              batched: bool) -> "LatticeStore":
        life = _joined_life(self.life, other.life)
        if batched and self._epochs() == other._epochs():
            # identical epochs per key ⇒ every value joins pointwise, so
            # the single-launch fast paths stay valid. Order: device-
            # resident columns (one scatter/fused launch, zero host
            # traffic), then the aligned host-stacked launch, then the
            # in-place host patch for subset deltas. An epoch mismatch
            # (reap/revive) lands in the general path below — which is
            # exactly the cache invalidation the lifecycle needs.
            if self.__dict__.get("_resident_cache") is not None:
                from ..kernels import resident
                fast = resident.try_join(self, other, life)
                if fast is not None:
                    return fast
            fast = _stacked_fast_join(self, other, life)
            if fast is not None:
                return fast
            fast = _patched_fast_join(self, other, life)
            if fast is not None:
                return fast
        a, b = self.as_dict(), other.as_dict()
        la, lb = dict(self.life), dict(other.life)
        out: Dict[str, Any] = {}
        pending: List[Tuple[str, Any, Any]] = []
        for k in set(a) | set(b):
            # lex product: only values at the winning epoch contribute —
            # a higher-epoch tombstone on either side absorbs the other
            ea = la.get(k, LIFE_BOTTOM)[0]
            eb = lb.get(k, LIFE_BOTTOM)[0]
            va = a.get(k) if ea >= eb else None
            vb = b.get(k) if eb >= ea else None
            if va is None and vb is None:
                continue
            if vb is None:
                out[k] = va
            elif va is None:
                out[k] = vb
            elif batched and _both_tensorstates(va, vb):
                pending.append((k, va, vb))
            else:
                out[k] = va.join(vb)
        if pending:
            out.update(_batched_join_tensorstates(pending))
        return LatticeStore(tuple(sorted(out.items())), life)

    def leq(self, other: "LatticeStore") -> bool:
        la, lb = dict(self.life), dict(other.life)
        b = other.as_dict()
        a = self.as_dict()
        for k in set(a) | set(la):
            ea, xa = la.get(k, LIFE_BOTTOM)
            eb, xb = lb.get(k, LIFE_BOTTOM)
            if ea > eb:
                return False
            if ea < eb:
                continue          # other's epoch absorbs this key entirely
            if xa > xb:
                return False
            v = a.get(k)
            if v is None:
                continue
            if k in b:
                if not v.leq(b[k]):
                    return False
            elif not _is_bottom(v):
                return False
        return True

    def __eq__(self, other: object) -> bool:
        if not isinstance(other, LatticeStore):
            return NotImplemented
        if dict(_canon_life(self.life)) != dict(_canon_life(other.life)):
            return False
        a, b = self.as_dict(), other.as_dict()
        for k in set(a) | set(b):
            if k not in a or k not in b:
                # absent key ≡ that key's ⊥
                if not _is_bottom(a.get(k, b.get(k))):
                    return False
            elif a[k] != b[k]:
                return False
        return True

    def __hash__(self):  # pragma: no cover
        raise TypeError("unhashable")

    def decompose(self) -> list:
        """Join-decomposition: per key, one lifecycle atom (when the key
        has non-bottom life) plus the embedded value's atoms (when it
        decomposes) each wrapped as a single-key store; else one atom per
        key. Value atoms of a past-0 epoch carry that epoch (with the
        expiry at bottom) so re-joining them lands in the right
        incarnation. Lets RemoveRedundant trim store payloads key-by-key
        (and finer, where the value supports it)."""
        atoms = []
        la = dict(self.life)
        for k, lv in self.life:
            atoms.append(LatticeStore((), ((k, lv),)))
        for k, v in self.entries:
            epoch = la.get(k, LIFE_BOTTOM)[0]
            lf = ((k, (epoch, LIFE_BOTTOM[1])),) if epoch else ()
            sub = getattr(v, "decompose", None)
            if sub is None:
                atoms.append(LatticeStore(((k, v),), lf))
            else:
                atoms.extend(LatticeStore(((k, a),), lf) for a in sub())
        return atoms

    def __repr__(self) -> str:
        inner = ", ".join(f"{k}: {type(v).__name__}" for k, v in self.entries)
        tombs = len(self.tombstoned_keys())
        extra = f", {tombs} tombstones" if tombs else ""
        return f"LatticeStore({{{inner}}}{extra})"


def _canon_life(items) -> Tuple[Tuple[str, Life], ...]:
    """Sorted life tuple with bottoms dropped (absent ≡ LIFE_BOTTOM)."""
    return tuple(sorted((k, lv) for k, lv in items if lv != LIFE_BOTTOM))


def _joined_life(a, b) -> Tuple[Tuple[str, Life], ...]:
    if not a:
        return _canon_life(b)
    if not b:
        return _canon_life(a)
    m = dict(a)
    for k, lv in b:
        cur = m.get(k)
        m[k] = lv if cur is None else life_join(cur, lv)
    return _canon_life(m.items())


# ---------------------------------------------------------------------------
# Batched TensorState join (one Pallas launch over many keys' chunks)
# ---------------------------------------------------------------------------

def _tensorstate_cls():
    """``TensorState``, imported on first use: pure-CRDT stores never
    pull in ``tensor_lattice`` (and with it jax)."""
    from .tensor_lattice import TensorState
    return TensorState


def _both_tensorstates(a: Any, b: Any) -> bool:
    ts = _tensorstate_cls()
    return isinstance(a, ts) and isinstance(b, ts)


def _stackable(act, bct) -> bool:
    if getattr(act, "is_sparse", False) or getattr(bct, "is_sparse", False):
        return False    # sparse deltas join via the gather/scatter path
    return (act.values.shape == bct.values.shape
            and act.values.dtype == bct.values.dtype)


class _StackedChunks:
    """Columnar cache of all of a store's TensorState chunk data: one
    ``[total_rows, chunk]`` values array + ``[total_rows]`` versions,
    with a ``(key, name, start, stop)`` layout. Built lazily on first
    batched join and attached to the (immutable) store, so a resident
    store that joins many deltas pays the stacking glue once; the output
    of a stacked join carries its own cache (its ChunkedTensors are views
    into the stacked result), keeping steady-state anti-entropy rounds at
    one kernel launch + O(keys) view assembly."""

    __slots__ = ("vals", "vers", "layout", "sig", "_spans")

    def __init__(self, vals, vers, layout, sig):
        self.vals = vals
        self.vers = vers
        self.layout = layout
        self.sig = sig
        self._spans = None

    @property
    def spans(self):
        """(key, name) → (start, stop) row-range lookup, built lazily —
        what the in-place patch path and the resident adopter index by."""
        if self._spans is None:
            self._spans = {(k, n): (s, e) for k, n, s, e in self.layout}
        return self._spans


def _stack_store(store: LatticeStore):
    """Fetch (or build and cache) the columnar view of ``store``. Returns
    None when the store is not stackable (non-tensor values, mixed chunk
    widths/dtypes, or empty)."""
    import numpy as np

    cached = store.__dict__.get("_stacked_cache")
    if cached is not None:
        return cached if isinstance(cached, _StackedChunks) else None
    ts_cls = _tensorstate_cls()
    result = None
    # cheap prescan first so non-tensor stores bail before any array work
    if store.entries and all(isinstance(v, ts_cls)
                             for _, v in store.entries):
        parts_v, parts_r, layout = [], [], []
        chunkw = dtype = vdtype = None
        row = 0
        ok = True
        for key, val in store.entries:
            for name, ct in val.chunks:
                if getattr(ct, "is_sparse", False):
                    ok = False    # sparse rows are not a dense column block
                    break
                v, r = np.asarray(ct.values), np.asarray(ct.versions)
                if chunkw is None:
                    chunkw, dtype, vdtype = v.shape[1], v.dtype, r.dtype
                elif (v.shape[1] != chunkw or v.dtype != dtype
                      or r.dtype != vdtype):
                    ok = False
                    break
                parts_v.append(v)
                parts_r.append(r)
                layout.append((key, name, row, row + v.shape[0]))
                row += v.shape[0]
            if not ok:
                break
        if ok and parts_v:
            # sig carries the full key sequence too: a key holding an
            # empty TensorState contributes no layout rows but must still
            # align between the two stores
            sig = (tuple(k for k, _ in store.entries),
                   tuple((k, n, stop - start)
                         for k, n, start, stop in layout),
                   chunkw, str(dtype), str(vdtype))
            result = _StackedChunks(np.concatenate(parts_v),
                                    np.concatenate(parts_r),
                                    tuple(layout), sig)
    object.__setattr__(store, "_stacked_cache",
                       result if result is not None else False)
    return result


def _stacked_fast_join(a_store: LatticeStore,
                       b_store: LatticeStore,
                       life: Tuple[Tuple[str, Life], ...] = ()):
    """Aligned-layout fast path: when both stores stack to the identical
    (key, name, rows) signature — the steady state of a resident store
    joining full-coverage deltas — the whole join is ONE kernel launch
    over the cached columns. Returns None when the layouts differ (the
    general per-segment path handles subsets and mismatches). ``life``
    is the pre-joined lifecycle component (the caller has already
    checked both sides agree on epochs, so values join pointwise)."""
    import numpy as np

    sa = _stack_store(a_store)
    if sa is None:
        return None
    sb = _stack_store(b_store)
    if sb is None or sa.sig != sb.sig:
        return None
    # jax-dependent imports only after stackability is established, so
    # pure-CRDT stores keep working where jax is unavailable
    from .tensor_lattice import ChunkedTensor, TensorState
    from ..kernels import ops

    if ops.use_pallas_default():
        import jax.numpy as jnp
        ovn, overn = ops.delta_join(
            jnp.asarray(sa.vals), jnp.asarray(sa.vers),
            jnp.asarray(sb.vals), jnp.asarray(sb.vers), interpret=False)
    else:
        n = sa.vals.shape[0]
        ov, over = ops.delta_join(sa.vals, sa.vers, sb.vals, sb.vers,
                                  block_n=n, interpret=True)
        ovn, overn = np.asarray(ov), np.asarray(over)

    out_entries = []
    li = 0
    layout = sa.layout
    for (key, A), (_, B) in zip(a_store.entries, b_store.entries):
        chunks = []
        for name, _ct in A.chunks:
            _, _, start, stop = layout[li]
            li += 1
            chunks.append((name, ChunkedTensor(ovn[start:stop],
                                               overn[start:stop])))
        out_entries.append((key, TensorState(tuple(chunks),
                                             max(A.lamport, B.lamport))))
    result = LatticeStore(tuple(out_entries), life)
    object.__setattr__(result, "_stacked_cache",
                       _StackedChunks(ovn, overn, layout, sa.sig))
    return result


def _patched_fast_join(a_store: LatticeStore,
                       b_store: LatticeStore,
                       life: Tuple[Tuple[str, Life], ...] = ()):
    """Host-cache patch path: ``a_store`` holds a stacked column cache
    and ``b_store`` touches a *subset* of its (key, tensor) spans with
    matching chunk counts — the single-key-write / sparse-delta case
    that previously invalidated the cache and re-``np.concatenate``'d
    the whole signature group on the next aligned join. Instead, copy
    the columns once and LWW-patch only the shipped rows in place;
    untouched keys reuse their entry objects outright. Returns None on
    any layout change (new key, new tensor, chunk-count drift) — only a
    real layout change pays the full rebuild."""
    import numpy as np

    sa = a_store.__dict__.get("_stacked_cache")
    if not isinstance(sa, _StackedChunks) or not b_store.entries:
        return None
    ts_cls = _tensorstate_cls()
    from .tensor_lattice import live_rows

    chunkw = sa.sig[2]
    vdtype = np.dtype(sa.sig[3])
    rdtype = np.dtype(sa.sig[4])
    a_map = dict(a_store.entries)
    # validation pass: every shipped tensor must land in an existing span
    patches = []           # (start, local idx, vals rows, vers rows)
    for key, val in b_store.entries:
        if not isinstance(val, ts_cls) or key not in a_map:
            return None
        for name, ct in val.chunks:
            span = sa.spans.get((key, name))
            if span is None:
                return None
            n_chunks, width = ct.shape
            if n_chunks != span[1] - span[0] or width != chunkw:
                return None
            li, lv, lr = live_rows(ct)
            lv, lr = np.asarray(lv), np.asarray(lr)
            if lv.dtype != vdtype or lr.dtype != rdtype:
                return None
            if li.size:
                patches.append((span[0], li, lv, lr))

    new_vals = sa.vals.copy()
    new_vers = sa.vers.copy()
    for start, li, lv, lr in patches:
        rows = li.astype(np.int64) + start
        take = lr > new_vers[rows]
        if take.any():
            rows = rows[take]
            new_vals[rows] = lv[take]
            new_vers[rows] = lr[take]

    from .tensor_lattice import ChunkedTensor, TensorState
    touched: Dict[str, Any] = {}
    for key, B in b_store.entries:
        A = a_map[key]
        b_names = frozenset(n for n, _ in B.chunks)
        chunks = []
        for name, ct in A.chunks:
            if name in b_names:
                start, stop = sa.spans[(key, name)]
                chunks.append((name, ChunkedTensor(new_vals[start:stop],
                                                   new_vers[start:stop])))
            else:
                chunks.append((name, ct))
        touched[key] = TensorState(tuple(chunks),
                                   max(A.lamport, B.lamport))

    entries = tuple((k, touched.get(k, v)) for k, v in a_store.entries)
    result = LatticeStore(entries, life)
    object.__setattr__(result, "_stacked_cache",
                       _StackedChunks(new_vals, new_vers, sa.layout,
                                      sa.sig))
    return result


def _batched_join_tensorstates(pairs: List[Tuple[str, Any, Any]]
                               ) -> Dict[str, Any]:
    """Join many (key, TensorState, TensorState) pairs with the chunk
    merges of *all* keys stacked into one kernel launch per (chunk-width,
    dtype) group, instead of one jit dispatch per key. Keys whose tensors
    cannot be stacked (shape/dtype mismatch) fall back to the per-key
    join."""
    from .tensor_lattice import ChunkedTensor, TensorState
    from ..kernels import ops

    out: Dict[str, Any] = {}
    segments: List[Tuple[Any, Any, Any, Any]] = []
    # per key: the merged (name, ChunkedTensor-or-segment-index) plan;
    # ``TensorState.chunks`` is sorted by name, so a linear sorted-tuple
    # merge avoids dict/set construction per key on the hot path
    plans: List[Tuple[str, list, int]] = []    # (key, plan, lamport)

    for key, A, B in pairs:
        ca, cb = A.chunks, B.chunks
        ia = ib = 0
        plan: list = []
        seg_start = len(segments)
        ok = True
        while ia < len(ca) or ib < len(cb):
            if ib == len(cb) or (ia < len(ca) and ca[ia][0] < cb[ib][0]):
                plan.append(ca[ia])
                ia += 1
            elif ia == len(ca) or cb[ib][0] < ca[ia][0]:
                plan.append(cb[ib])
                ib += 1
            else:                              # same tensor on both sides
                name, act = ca[ia]
                bct = cb[ib][1]
                if not _stackable(act, bct):
                    ok = False
                    break
                plan.append((name, len(segments)))
                segments.append((act.values, act.versions,
                                 bct.values, bct.versions))
                ia += 1
                ib += 1
        if not ok:
            del segments[seg_start:]           # discard this key's segments
            out[key] = A.join(B)               # per-key fallback
            continue
        plans.append((key, plan, max(A.lamport, B.lamport)))

    results: List[Any] = []
    if segments:
        if ops.use_pallas_default():
            # TPU: stay on-device, compiled Mosaic kernel
            results = ops.batched_delta_join(segments, interpret=False)
        else:
            # CPU: host-staged numpy glue + one single-grid-step
            # interpret launch per signature (outputs are numpy views)
            results = ops.batched_delta_join(segments, interpret=True,
                                             host_stage=True)

    for key, plan, lamport in plans:
        chunks = tuple(
            (name, ChunkedTensor(*results[v]) if isinstance(v, int) else v)
            for name, v in plan)
        out[key] = TensorState(chunks, lamport)
    return out


# ---------------------------------------------------------------------------
# Store-wide digest selection (the DigestBudget policy over keyed stores)
# ---------------------------------------------------------------------------

def digest_select_store(store: LatticeStore, budget_bytes: int,
                        interpret: bool = True) -> LatticeStore:
    """Byte-budgeted chunk selection across the *whole* store: chunks from
    every ``TensorState`` value under every key enter ONE global energy
    ranking (``tensor_lattice.digest_keep_plan``, scope = store key) — so
    the budget picks *keys* by digest, not just chunks within one object.
    Non-tensor values pass through untouched (they are not
    chunk-addressable; the policy budgets tensor payload). Lifecycle
    state rides through whole — trimming a tombstone or expiry to save a
    few bytes would only delay its propagation. The result is
    ≤ ``store`` pointwise, so joining it is always safe."""
    from .tensor_lattice import (TensorState, digest_keep_plan,
                                 mask_kept_chunks)

    passthrough: Dict[str, Any] = {}
    tensor_keys: Dict[str, Any] = {}
    for key, val in store.as_dict().items():
        (tensor_keys if isinstance(val, TensorState)
         else passthrough)[key] = val

    cache = store.__dict__.get("_resident_cache")
    if cache is not None:
        # resident stores rank from the digest columns the join kernels
        # keep fresh: one top-k epilogue, no per-tensor recompute
        from ..kernels import resident
        keep = resident.keep_plan(cache, budget_bytes)
    else:
        keep = digest_keep_plan(
            ((key, name, ct) for key, val in tensor_keys.items()
             for name, ct in val.as_dict().items()), budget_bytes,
            interpret)
    if keep is None:
        return store

    out: Dict[str, Any] = dict(passthrough)
    for key, val in tensor_keys.items():
        kept = {name: mask_kept_chunks(ct, keep[(key, name)])
                for name, ct in val.as_dict().items()
                if keep.get((key, name))}
        if kept:
            out[key] = TensorState.of(kept, lamport=val.lamport)
    return LatticeStore(tuple(sorted(out.items())), store.life)
