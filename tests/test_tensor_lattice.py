"""Tensor lattices: the δ-CRDT bridge to ML training state.

Checks: (i) the versioned chunk store is a join-semilattice and satisfies
the decomposition law for chunk writes; (ii) the sparse wire format
round-trips and realizes size(mᵟ(X)) ≪ size(X); (iii) the additive dot
store is duplicate-safe; (iv) the §7.2-compressed IntervalSum is EXACTLY
the dot store under causal (Algorithm-2-style) delivery."""

import random

import jax
import jax.numpy as jnp
import numpy as np
import pytest
import pytest as _pytest
_pytest.importorskip(
    "hypothesis", reason="dev dependency — pip install -r requirements-dev.txt")
from hypothesis import given, settings, strategies as st

from repro.core.tensor_lattice import (ChunkedTensor, DotSumStore,
                                       IntervalSum, TensorState, chunk_tensor,
                                       pack_delta, packed_size_bytes,
                                       sparse_chunks, unchunk, unpack_delta)

NAMES = ["w1", "w2"]
N_CHUNKS = 4
CHUNK = 8


def _random_states(seed, n_replicas=3, n_ops=10):
    rng = random.Random(seed)
    nprng = np.random.default_rng(seed)
    states = [TensorState.bottom() for _ in range(n_replicas)]
    # initialise all replicas with the same bottom-version tensors
    init = {}
    for nm in NAMES:
        ct = chunk_tensor(np.zeros(N_CHUNKS * CHUNK, np.float32), CHUNK)
        init[nm] = ct
    states = [TensorState.of(init) for _ in range(n_replicas)]
    for _ in range(n_ops):
        r = rng.randrange(n_replicas)
        if rng.random() < 0.7:
            nm = rng.choice(NAMES)
            k = rng.randint(1, N_CHUNKS)
            idx = nprng.choice(N_CHUNKS, size=k, replace=False)
            vals = nprng.normal(size=(k, CHUNK)).astype(np.float32)
            d = states[r].write_delta(r, nm, vals, chunk_idx=idx)
            states[r] = states[r].join(d)
        else:
            src = rng.randrange(n_replicas)
            states[r] = states[r].join(states[src])
    return states


@settings(max_examples=25, deadline=None)
@given(seed=st.integers(0, 2**31 - 1))
def test_tensorstate_lattice_laws(seed):
    a, b, c = _random_states(seed)
    assert a.join(a) == a
    assert a.join(b) == b.join(a)
    assert a.join(b).join(c) == a.join(b.join(c))
    assert a.join(TensorState.bottom()) == a
    assert a.leq(a.join(b)) and b.leq(a.join(b))


@settings(max_examples=25, deadline=None)
@given(seed=st.integers(0, 2**31 - 1))
def test_tensorstate_write_decomposition(seed):
    rng = np.random.default_rng(seed)
    X = _random_states(seed)[0]
    idx = rng.choice(N_CHUNKS, size=2, replace=False)
    vals = rng.normal(size=(2, CHUNK)).astype(np.float32)
    full = X.write_full(1, "w1", vals, chunk_idx=idx)
    delta = X.write_delta(1, "w1", vals, chunk_idx=idx)
    assert full == X.join(delta)          # m(X) = X ⊔ mᵟ(X)
    # the delta really applied
    got = np.asarray(unchunk(full.as_dict()["w1"], (N_CHUNKS, CHUNK)))
    assert np.allclose(got[idx], vals)


def test_pack_delta_is_sparse_and_roundtrips():
    X = _random_states(0)[0]
    idx = np.array([2])
    vals = np.ones((1, CHUNK), np.float32)
    delta = X.write_delta(0, "w1", vals, chunk_idx=idx)
    wire = pack_delta(delta)
    assert list(wire["tensors"].keys()) == ["w1"]
    assert wire["tensors"]["w1"][0].tolist() == [2]  # only the touched chunk
    rt = unpack_delta(wire)
    assert X.join(rt) == X.join(delta)
    # sparse payload ≪ dense full state
    full_state_bytes = sum(np.asarray(ct.values).nbytes
                           for _, ct in X.chunks)
    assert packed_size_bytes(wire) < full_state_bytes / 4


def test_pack_delta_respects_known_versions():
    X = _random_states(3)[0]
    d1 = X.write_delta(0, "w1", np.ones((1, CHUNK), np.float32),
                       chunk_idx=np.array([1]))
    X2 = X.join(d1)
    known = {nm: np.asarray(ct.versions) for nm, ct in X2.chunks}
    d2 = X2.write_delta(0, "w2", np.ones((1, CHUNK), np.float32),
                        chunk_idx=np.array([3]))
    # shipping (d1 ⊔ d2) to a receiver that already has X2: only d2 survives
    wire = pack_delta(d1.join(d2), known_versions=known)
    assert set(wire["tensors"]) == {"w2"}


def test_version_tie_break_is_deterministic():
    """Concurrent writes to the same chunk: higher (lamport, rank) wins on
    BOTH replicas — convergence despite conflict."""
    base = _random_states(1)[0]
    da = base.write_delta(0, "w1", np.full((1, CHUNK), 7, np.float32),
                          chunk_idx=np.array([0]))
    db = base.write_delta(1, "w1", np.full((1, CHUNK), 9, np.float32),
                          chunk_idx=np.array([0]))
    ab = base.join(da).join(db)
    ba = base.join(db).join(da)
    assert ab == ba
    got = np.asarray(unchunk(ab.as_dict()["w1"], (N_CHUNKS, CHUNK)))[0]
    assert np.allclose(got, 9)  # same lamport, rank 1 > rank 0


def _chunks(n_chunks, width, dtype, sparse):
    vals = np.ones((n_chunks, width), dtype)
    vers = np.full((n_chunks,), 3, np.int32)
    if sparse:
        return sparse_chunks(n_chunks, np.arange(n_chunks), vals, vers)
    return ChunkedTensor(vals, vers)


@pytest.mark.parametrize("other", [(2, 8, np.float32), (1, 4, np.float32),
                                   (1, 8, np.int32)],
                         ids=["chunk-count", "width", "dtype"])
@pytest.mark.parametrize("a_sparse,b_sparse", [(False, False),
                                               (False, True),
                                               (True, True)],
                         ids=["dense-dense", "dense-sparse", "sparse-sparse"])
def test_join_of_mismatched_chunk_layouts_raises(other, a_sparse, b_sparse):
    """One tensor name holds one ``[n_chunks, chunk]`` layout and dtype;
    joining two others is a type error in every density mix, in either
    order, never a broadcast or an index past the end."""
    a = TensorState.of({"w": _chunks(1, 8, np.float32, a_sparse)})
    b = TensorState.of({"w": _chunks(*other, b_sparse)})
    with pytest.raises(ValueError, match="cannot join chunk tensors"):
        a.join(b)
    with pytest.raises(ValueError, match="cannot join chunk tensors"):
        b.join(a)


# ---------------------------------------------------------------------------
# Additive dot store + compression
# ---------------------------------------------------------------------------

def _upd(seed):
    rng = np.random.default_rng(seed)
    return {"a": jnp.asarray(rng.normal(size=(3,)).astype(np.float32))}


def test_dotsum_duplicate_and_reorder_safe():
    S = DotSumStore.bottom()
    d1 = S.contribute_delta("p0", _upd(1))
    S1 = S.join(d1)
    d2 = S1.contribute_delta("p0", _upd(2))
    # deliver in both orders, with duplicates
    X = DotSumStore.bottom().join(d2).join(d1).join(d2).join(d1)
    Y = DotSumStore.bottom().join(d1).join(d2)
    assert X == Y
    want = _upd(1)["a"] + _upd(2)["a"]
    assert np.allclose(np.asarray(X.total()["a"]), np.asarray(want))


def _one_dot(payload, dot=("p0", 1)):
    return DotSumStore(((dot, payload),))


def _eq_bytes():
    from repro.obs import global_registry
    got = global_registry().snapshot().get("repro_dotstore_eq_bytes_total")
    return {w: (got or {}).get(w, 0.0) for w in ("device", "host")}


def _array_equal_eq(a, b):
    """What equality was before it moved to the device: every leaf pair
    compared with ``np.array_equal`` on the host."""
    da, db = a.as_dict(), b.as_dict()
    if set(da) != set(db):
        return False
    for k in da:
        la, ta = jax.tree_util.tree_flatten(da[k])
        lb, tb = jax.tree_util.tree_flatten(db[k])
        if ta != tb or not all(np.array_equal(np.asarray(x), np.asarray(y))
                               for x, y in zip(la, lb)):
            return False
    return True


def _bf16(values):
    return jnp.asarray(np.asarray(values, np.float32)).astype(jnp.bfloat16)


_NAN = _one_dot({"w": jnp.asarray([1.0, np.nan], jnp.float32)})


@pytest.mark.parametrize("a,b,equal,where", [
    pytest.param(_one_dot({"w": _bf16([1, 2, 3])}),
                 _one_dot({"w": _bf16([1, 2, 3]) + 0}), True, "device",
                 id="bf16-distinct-buffers"),
    pytest.param(_one_dot({"w": _bf16([1, 2, 3])}),
                 _one_dot({"w": _bf16([1, 2, 4])}), False, "device",
                 id="one-element-differs"),
    pytest.param(_NAN, _NAN, False, "device", id="nan-same-object"),
    pytest.param(_one_dot({"w": jnp.zeros((2, 3))}),
                 _one_dot({"w": jnp.zeros((3, 2))}), False, None,
                 id="shape-mismatch"),
    pytest.param(_one_dot({"w": jnp.zeros(3)}),
                 _one_dot({"v": jnp.zeros(3)}), False, None,
                 id="treedef-mismatch"),
    pytest.param(_one_dot({"w": np.arange(3.0), "b": np.float32(1)}),
                 _one_dot({"w": np.arange(3.0), "b": np.float32(1)}), True,
                 "host", id="numpy-both-sides"),
    pytest.param(_one_dot({"w": np.arange(3, dtype=np.float32)}),
                 _one_dot({"w": jnp.arange(3, dtype=jnp.float32)}), True,
                 "device", id="numpy-against-jax"),
    pytest.param(_one_dot({"w": _bf16([1, 2])}),
                 _one_dot({"w": jnp.asarray([1, 2], jnp.float32)}), True,
                 "device", id="bf16-against-f32-promotes"),
    pytest.param(_one_dot({"w": _bf16([1, 2])}),
                 _one_dot({"w": jnp.asarray([1, 2.001], jnp.float32)}),
                 False, "device", id="promoted-values-differ"),
    pytest.param(_one_dot({"w": jnp.asarray([16777217], jnp.int32)}),
                 _one_dot({"w": np.asarray([16777216], np.float32)}), False,
                 "host", id="promotion-past-the-device-stays-on-host"),
    pytest.param(_one_dot({"w": jnp.zeros(3)}),
                 _one_dot({"w": jnp.zeros(3)}, dot=("p1", 1)), False, None,
                 id="different-dot-sets"),
])
def test_dotsum_eq_is_array_equal_on_every_leaf(a, b, equal, where):
    """Equality keeps what ``np.array_equal`` decides on every leaf pair,
    NaN unequal to itself included; pairs with a device array are compared
    there, numpy pairs on the host, and an early exit compares nothing."""
    before = _eq_bytes()
    assert (a == b) is equal
    assert _array_equal_eq(a, b) is equal
    moved = {w: _eq_bytes()[w] - before[w] for w in before}
    compared = sum(getattr(x, "nbytes", 0) for s in (a, b) for _, p in s.dots
                   for x in jax.tree_util.tree_leaves(p))
    assert moved == {w: (compared if w == where else 0.0) for w in moved}


@settings(max_examples=20, deadline=None)
@given(seed=st.integers(0, 2**31 - 1))
def test_dotsum_lattice_laws(seed):
    rng = random.Random(seed)
    stores = [DotSumStore.bottom() for _ in range(3)]
    for k in range(10):
        r = rng.randrange(3)
        if rng.random() < 0.7:
            d = stores[r].contribute_delta(f"p{r}", _upd(seed + k))
            stores[r] = stores[r].join(d)
        else:
            stores[r] = stores[r].join(stores[rng.randrange(3)])
    a, b, c = stores
    assert a.join(b) == b.join(a)
    assert a.join(b).join(c) == a.join(b.join(c))
    assert a.join(a) == a


@settings(max_examples=20, deadline=None)
@given(seed=st.integers(0, 2**31 - 1))
def test_interval_sum_matches_dot_store_under_causal_delivery(seed):
    """§7.2 compression exactness: deliver per-producer delta-intervals with
    duplications and rejected gaps; the (vv, sum) encoding must equal the
    explicit dot store."""
    rng = random.Random(seed)
    ref = DotSumStore.bottom()
    agg = IntervalSum()
    producers = ["p0", "p1"]
    produced = {p: [] for p in producers}
    for k in range(20):
        p = rng.choice(producers)
        upd = _upd(seed * 31 + k)
        produced[p].append(upd)
        ref = ref.join(ref.contribute_delta(p, upd))
        # attempt deliveries in random order, incl. duplicates and gaps
        for _ in range(rng.randint(1, 3)):
            q = rng.choice(producers)
            if not produced[q]:
                continue
            a = rng.randint(1, len(produced[q]) + 1)
            b = rng.randint(a, len(produced[q]) + 1)
            applied = agg.apply_interval(q, a, produced[q][a - 1:b - 1])
            # gaps must be rejected (causal delta-merging condition)
            if a - 1 > agg.prefix.get(q, 0):
                assert not applied or a - 1 <= agg.prefix.get(q, 0)
    # final anti-entropy: deliver everything in order
    for p in producers:
        agg.apply_interval(p, 1, produced[p])
    assert agg.matches(ref, atol=1e-4)
