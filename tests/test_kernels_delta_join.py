"""delta_join / chunk_digest Pallas kernels vs oracles + lattice-law checks
of the kernel itself (the kernel IS the join, so it must satisfy the join
laws), plus integration with the TensorState lattice."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:        # only the property sweep needs hypothesis (dev dependency)
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False

from repro.kernels import ops


def _mk(n, chunk, dtype, seed):
    rng = np.random.default_rng(seed)
    vals = rng.normal(size=(n, chunk)).astype(np.float32)
    vers = rng.integers(0, 50, size=(n,)).astype(np.int32)
    return jnp.asarray(vals, dtype), jnp.asarray(vers)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("n,chunk,bn", [
    (256, 128, 128), (1024, 256, 256), (64, 512, 64), (8, 128, 8),
])
def test_delta_join_matches_ref(dtype, n, chunk, bn):
    av, avers = _mk(n, chunk, dtype, 0)
    bv, bvers = _mk(n, chunk, dtype, 1)
    ov, overs = ops.delta_join(av, avers, bv, bvers, block_n=bn,
                               interpret=True)
    rv, rvers = ops.delta_join_ref(av, avers, bv, bvers)
    np.testing.assert_array_equal(np.asarray(ov), np.asarray(rv))
    np.testing.assert_array_equal(np.asarray(overs), np.asarray(rvers))


if HAVE_HYPOTHESIS:
    _property = lambda f: settings(max_examples=20, deadline=None)(
        given(seed=st.integers(0, 2**31 - 1))(f))
else:
    _property = pytest.mark.skip(
        reason="dev dependency — pip install -r requirements-dev.txt")


@_property
def test_delta_join_kernel_is_a_join(seed):
    """Kernel-level lattice laws: idempotent / commutative / associative.
    (Ties must carry equal values, as the TensorState lattice guarantees.)"""
    rng = np.random.default_rng(seed)
    n, chunk = 64, 128
    # versions drawn so that equal versions ⇒ equal values (the lattice
    # precondition): derive each chunk's values from its version
    vers = rng.integers(0, 6, size=(3, n)).astype(np.int32)
    vals = vers[..., None].astype(np.float32) * np.ones((1, 1, chunk),
                                                        np.float32)
    a, b, c = [(jnp.asarray(vals[i]), jnp.asarray(vers[i])) for i in range(3)]

    def J(x, y):
        return ops.delta_join(x[0], x[1], y[0], y[1], block_n=n,
                              interpret=True)

    def eq(x, y):
        return (np.array_equal(np.asarray(x[0]), np.asarray(y[0]))
                and np.array_equal(np.asarray(x[1]), np.asarray(y[1])))

    assert eq(J(a, a), a)                      # idempotent
    assert eq(J(a, b), J(b, a))                # commutative
    assert eq(J(J(a, b), c), J(a, J(b, c)))    # associative


@pytest.mark.parametrize("n,chunk,bn", [
    (100, 128, 32),    # n not a multiple of the block
    (7, 128, 8),       # n smaller than the block
    (1000, 128, 256),  # large ragged tail
    (13, 256, 13),     # bn == n exactly (no padding)
])
def test_delta_join_ragged_chunk_counts_match_ref(n, chunk, bn):
    """Chunk counts that are NOT multiples of the block size: the kernel
    zero-pads to the block boundary (⊥ versions) and slices back."""
    av, avers = _mk(n, chunk, jnp.float32, 2)
    bv, bvers = _mk(n, chunk, jnp.float32, 3)
    ov, overs = ops.delta_join(av, avers, bv, bvers, block_n=bn,
                               interpret=True)
    rv, rvers = ops.delta_join_ref(av, avers, bv, bvers)
    assert ov.shape == (n, chunk) and overs.shape == (n,)
    np.testing.assert_array_equal(np.asarray(ov), np.asarray(rv))
    np.testing.assert_array_equal(np.asarray(overs), np.asarray(rvers))


@pytest.mark.parametrize("sizes", [
    [4, 4, 4],                 # uniform — one stacked launch
    [1, 3, 7, 13, 5],          # ragged segment lengths
    [8],                       # single segment
])
def test_batched_delta_join_interpret_parity_with_ref(sizes):
    """Stacked multi-segment launch == per-segment oracle, on CPU in
    interpret mode (the satellite's interpret-mode parity check)."""
    segs = []
    for i, n in enumerate(sizes):
        av, avers = _mk(n, 128, jnp.float32, 10 + i)
        bv, bvers = _mk(n, 128, jnp.float32, 50 + i)
        segs.append((av, avers, bv, bvers))
    outs = ops.batched_delta_join(segs, block_n=8, interpret=True)
    refs = ops.batched_delta_join_ref(segs)
    assert len(outs) == len(segs)
    for (ov, overs), (rv, rvers), (av, _, _, _) in zip(outs, refs, segs):
        assert ov.shape == av.shape
        np.testing.assert_array_equal(np.asarray(ov), np.asarray(rv))
        np.testing.assert_array_equal(np.asarray(overs), np.asarray(rvers))


def test_batched_delta_join_groups_mixed_signatures():
    """Segments with different chunk widths / dtypes cannot share a
    launch; grouping must still return per-segment results in order."""
    segs = []
    for i, (n, chunk, dt) in enumerate([(4, 128, jnp.float32),
                                        (6, 256, jnp.float32),
                                        (4, 128, jnp.bfloat16),
                                        (10, 128, jnp.float32)]):
        av, avers = _mk(n, chunk, dt, 20 + i)
        bv, bvers = _mk(n, chunk, dt, 80 + i)
        segs.append((av, avers, bv, bvers))
    outs = ops.batched_delta_join(segs, interpret=True)
    refs = ops.batched_delta_join_ref(segs)
    for (ov, overs), (rv, rvers) in zip(outs, refs):
        np.testing.assert_array_equal(np.asarray(ov), np.asarray(rv))
        np.testing.assert_array_equal(np.asarray(overs), np.asarray(rvers))


@pytest.mark.parametrize("n,chunk,bn", [(256, 128, 128), (32, 256, 32),
                                        (100, 128, 32), (5, 128, 8)])
def test_chunk_digest_matches_ref(n, chunk, bn):
    x, _ = _mk(n, chunk, jnp.float32, 7)
    ma, ss = ops.chunk_digest(x, block_n=bn, interpret=True)
    rma, rss = ops.chunk_digest_ref(x)
    np.testing.assert_allclose(np.asarray(ma), np.asarray(rma), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(ss), np.asarray(rss), rtol=1e-5)


def test_kernel_join_equals_tensorstate_join():
    """End-to-end: the Pallas join produces exactly the TensorState join."""
    from repro.core.tensor_lattice import (ChunkedTensor, TensorState,
                                           chunk_tensor)
    rng = np.random.default_rng(3)
    n, chunk = 16, 128
    a_vals = rng.normal(size=(n, chunk)).astype(np.float32)
    b_vals = rng.normal(size=(n, chunk)).astype(np.float32)
    a_vers = rng.integers(0, 5, size=(n,)).astype(np.int32)
    b_vers = rng.integers(0, 5, size=(n,)).astype(np.int32)
    # ties must agree (lattice precondition)
    tie = a_vers == b_vers
    b_vals[tie] = a_vals[tie]

    A = TensorState.of({"w": ChunkedTensor(jnp.asarray(a_vals),
                                           jnp.asarray(a_vers))})
    B = TensorState.of({"w": ChunkedTensor(jnp.asarray(b_vals),
                                           jnp.asarray(b_vers))})
    lattice_join = A.join(B).as_dict()["w"]
    kv, kvers = ops.delta_join(jnp.asarray(a_vals), jnp.asarray(a_vers),
                               jnp.asarray(b_vals), jnp.asarray(b_vers),
                               block_n=n, interpret=True)
    np.testing.assert_array_equal(np.asarray(lattice_join.values),
                                  np.asarray(kv))
    np.testing.assert_array_equal(np.asarray(lattice_join.versions),
                                  np.asarray(kvers))


# ---------------------------------------------------------------------------
# Fused join+digest and scatter-ingest (the resident-store kernels)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("n,chunk,bn", [
    (64, 128, 32), (100, 128, 32),   # ragged row count
    (7, 256, 8), (13, 128, 13),
])
def test_fused_join_digest_matches_ref(dtype, n, chunk, bn):
    av, avers = _mk(n, chunk, dtype, 30)
    bv, bvers = _mk(n, chunk, dtype, 31)
    ov, overs, ma, ss = ops.fused_join_digest(av, avers, bv, bvers,
                                              block_n=bn, interpret=True)
    rv, rvers, rma, rss = ops.fused_join_digest_ref(av, avers, bv, bvers)
    np.testing.assert_array_equal(np.asarray(ov), np.asarray(rv))
    np.testing.assert_array_equal(np.asarray(overs), np.asarray(rvers))
    np.testing.assert_allclose(np.asarray(ma), np.asarray(rma), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(ss), np.asarray(rss), rtol=1e-5)


def test_fused_join_digest_auto_dispatch_matches_interpret():
    """interpret=None (the hot-path default: XLA oracle on CPU) computes
    exactly what the interpret-mode Pallas kernel computes."""
    av, avers = _mk(24, 128, jnp.float32, 32)
    bv, bvers = _mk(24, 128, jnp.float32, 33)
    auto = ops.fused_join_digest(av, avers, bv, bvers)
    pallas = ops.fused_join_digest(av, avers, bv, bvers, interpret=True)
    for x, y in zip(auto, pallas):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y), rtol=1e-5)


def _mk_scatter(n, r, chunk, seed, vdtype=np.float32):
    rng = np.random.default_rng(seed)
    vals = jnp.asarray(rng.normal(size=(n, chunk)).astype(vdtype))
    vers = jnp.asarray(rng.integers(0, 50, size=(n,)).astype(np.int32))
    ma, ss = ops.chunk_digest_ref(vals)
    idx = np.sort(rng.choice(n, size=r, replace=False)).astype(np.int32)
    d_vals = jnp.asarray(rng.normal(size=(r, chunk)).astype(vdtype))
    d_vers = jnp.asarray(rng.integers(0, 80, size=(r,)).astype(np.int32))
    return vals, vers, ma, ss, jnp.asarray(idx), d_vals, d_vers


@pytest.mark.parametrize("n,r,chunk", [
    (32, 5, 128), (64, 64, 128),     # full coverage
    (17, 3, 256), (8, 1, 128),
])
def test_scatter_join_matches_ref(n, r, chunk):
    args = _mk_scatter(n, r, chunk, 40)
    outs = ops.scatter_join(*args, interpret=True)
    refs = ops.scatter_join_ref(*args)
    for x, y in zip(outs, refs):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y), rtol=1e-5)


def test_scatter_join_preserves_untouched_rows():
    """Rows not listed in idx come back bit-identical (the aliased
    in-place contract of the resident columns)."""
    vals, vers, ma, ss, idx, d_vals, d_vers = _mk_scatter(40, 4, 128, 41)
    ov, overs, oma, oss = ops.scatter_join(vals, vers, ma, ss, idx,
                                           d_vals, d_vers, interpret=True)
    touched = set(np.asarray(idx).tolist())
    keep = np.array([i for i in range(40) if i not in touched])
    np.testing.assert_array_equal(np.asarray(ov)[keep],
                                  np.asarray(vals)[keep])
    np.testing.assert_array_equal(np.asarray(overs)[keep],
                                  np.asarray(vers)[keep])
    np.testing.assert_array_equal(np.asarray(oma)[keep],
                                  np.asarray(ma)[keep])
    np.testing.assert_array_equal(np.asarray(oss)[keep],
                                  np.asarray(ss)[keep])


def test_scatter_join_empty_idx_is_a_launch_free_noop():
    vals, vers, ma, ss, _, _, _ = _mk_scatter(16, 2, 128, 42)
    empty = jnp.zeros((0,), jnp.int32)
    snap = ops.counters.snapshot()
    outs = ops.scatter_join(vals, vers, ma, ss, empty,
                            jnp.zeros((0, 128), vals.dtype),
                            jnp.zeros((0,), vers.dtype))
    assert ops.counters.since(snap)["launches"] == 0
    assert outs[0] is vals and outs[1] is vers


def test_scatter_join_auto_dispatch_matches_interpret():
    args = _mk_scatter(30, 6, 128, 43)
    auto = ops.scatter_join(*args)
    pallas = ops.scatter_join(*args, interpret=True)
    for x, y in zip(auto, pallas):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y), rtol=1e-5)


def test_counters_count_launches_and_numpy_staging_only():
    """One wrapper call = one launch; numpy operands count their nbytes
    as host→device staging, device-resident jax.Arrays count zero."""
    av_np = np.random.default_rng(44).normal(size=(8, 128)) \
        .astype(np.float32)
    avers_np = np.ones((8,), np.int32)
    snap = ops.counters.snapshot()
    ops.fused_join_digest(av_np, avers_np, av_np, avers_np)
    d = ops.counters.since(snap)
    assert d["launches"] == 1
    assert d["h2d_bytes"] == 2 * (av_np.nbytes + avers_np.nbytes)
    av, avers = jnp.asarray(av_np), jnp.asarray(avers_np)
    snap = ops.counters.snapshot()
    ops.fused_join_digest(av, avers, av, avers)
    d = ops.counters.since(snap)
    assert d["launches"] == 1 and d["h2d_bytes"] == 0


def test_scatter_join_merges_rows_sharing_a_block():
    """Unsorted delta rows packed into a few 8-row value blocks and one
    1024-row column block: every row's merge survives its block-mates'."""
    rng = np.random.default_rng(45)
    n, chunk = 3000, 128
    vals = jnp.asarray(rng.normal(size=(n, chunk)).astype(np.float32))
    vers = jnp.asarray(rng.integers(0, 50, size=(n,)).astype(np.int32))
    ma, ss = ops.chunk_digest_ref(vals)
    idx = rng.permutation(np.r_[np.arange(1000, 1024), np.arange(8, 24),
                                [2999]]).astype(np.int32)
    d_vals = jnp.asarray(rng.normal(size=(idx.size, chunk))
                         .astype(np.float32))
    d_vers = jnp.asarray(rng.integers(0, 80, size=(idx.size,))
                         .astype(np.int32))
    args = (vals, vers, ma, ss, jnp.asarray(idx), d_vals, d_vers)
    for x, y in zip(ops.scatter_join(*args, interpret=True),
                    ops.scatter_join_ref(*args)):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y), rtol=1e-5)


def test_scatter_join_splits_long_deltas_across_launches(monkeypatch):
    """More delta rows than one launch's SMEM holds: consecutive launches
    chain over the aliased columns and give the one-launch answer."""
    from repro.kernels import delta_join as dj
    args = _mk_scatter(64, 40, 128, 46)
    monkeypatch.setattr(dj, "_SCATTER_ROWS", 16)
    outs = dj.scatter_join(*args, interpret=True)
    for x, y in zip(outs, ops.scatter_join_ref(*args)):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y), rtol=1e-5)
