"""δ-CRDT versioned-chunk join + chunk digest (Pallas TPU kernels).

These are the paper's hot loops at TPU scale. When a pod joins a received
delta (possibly multi-GB of parameter chunks) into resident state, the
naive XLA lowering is a compare → broadcast-select → max chain, i.e. three
passes over HBM. The join is purely bandwidth-bound (arithmetic intensity
≈ 0), so fusing it into ONE tiled pass over HBM is the whole optimization:

* ``delta_join``   — out[i] = b[i] if b_ver[i] > a_ver[i] else a[i];
                     out_ver = max(a_ver, b_ver). One load of each operand
                     tile into VMEM, one store.
* ``chunk_digest`` — per-chunk max|x| and Σx² in one pass; the anti-entropy
                     layer uses digests to pick which chunks enter the next
                     delta (top-magnitude shipping) without a second sweep
                     over the tensor.
* ``fused_join_digest`` — join + digest of the merged result in the same
                     pass: the merged tile is already in VMEM, so the next
                     round's chunk ranking costs no extra HBM traffic.
* ``scatter_join``  — sparse ingest: a prefetched index column drives the
                     grid over *delta* rows, merging each shipped row into
                     the resident stacked columns (and refreshing its
                     digest row) in place via ``input_output_aliases`` —
                     O(shipped rows) touched, O(1) launches, regardless of
                     store size. The device half of ``kernels/resident``.

Layout. Per-chunk columns (versions, digests) are ``[n]`` vectors, which
XLA tiles on TPU in 1024-element tiles. The kernels view them as
``[n / 128, 128]`` — the same bytes in the same order, so the reshape is a
bitcast — and move them in ``(8, 128)`` blocks: one such block covers
``ROW_TILE`` = 1024 chunk rows. A ``[n, 1]`` column would instead be
padded to 128 lanes per row in HBM. Inside a kernel, one lane row of
versions becomes the sublane column that selects 128 value rows (and a
digest column becomes a lane row again) through the diagonal of a
128 × 128 tile: a select plus a reduction, both of which Mosaic lowers.
Row counts are padded to ``ROW_TILE``; the resident store allocates its
columns padded, so its launches never copy.

jnp oracles in ``ref.py``; jit'd wrappers with ``interpret=`` in ``ops.py``.
"""

from __future__ import annotations

import functools
from typing import List, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128                  # lane width of a vreg / of the column views
ROW_TILE = 8 * LANES         # chunk rows one (8, 128) column block covers
_BLOCK_BYTES = 1 << 20       # value bytes per operand block in VMEM
_SCATTER_ROWS = 1 << 16      # delta rows per scatter launch: its index and
                             # version columns sit in the 1 MiB of SMEM


def padded_rows(n: int) -> int:
    """``n`` rounded up to whole ``ROW_TILE`` column blocks."""
    return -(-n // ROW_TILE) * ROW_TILE


def _pad_rows(x: jax.Array, pad: int, fill=0) -> jax.Array:
    """Pad the leading (chunk-count) axis by ``pad`` rows of ``fill``.
    Padded versions are 0 == ⊥, so padded rows never win a merge and the
    digest of a padded row is 0; outputs are sliced back to the true
    length."""
    if pad == 0:
        return x
    return jnp.pad(x, [(0, pad)] + [(0, 0)] * (x.ndim - 1),
                   constant_values=fill)


def _col_view(x: jax.Array) -> jax.Array:
    return x.reshape(-1, LANES)


def _blocks(n: int, chunk: int, itemsize: int, block_n: int
            ) -> Tuple[int, int, int]:
    """``(rows per grid step, padded rows, columns per grid step)``: row
    blocks are whole ``ROW_TILE``s (at least ``block_n`` rows, at most the
    padded array); column blocks halve while a block exceeds
    ``_BLOCK_BYTES`` and stays a multiple of 128 lanes."""
    bn = padded_rows(max(1, min(block_n, n)))
    np_ = -(-n // bn) * bn
    cw = chunk
    while cw % (2 * LANES) == 0 and bn * cw * itemsize > _BLOCK_BYTES:
        cw //= 2
    return bn, np_, cw


def _eye() -> jax.Array:
    return (jax.lax.broadcasted_iota(jnp.int32, (LANES, LANES), 0)
            == jax.lax.broadcasted_iota(jnp.int32, (LANES, LANES), 1))


def _row_to_col(row: jax.Array, eye: jax.Array) -> jax.Array:
    """(1, 128) lane row of non-negative values → (128, 1) column."""
    return jnp.max(jnp.where(eye, jnp.broadcast_to(row, eye.shape), 0),
                   axis=1, keepdims=True)


def _col_to_row(col: jax.Array, eye: jax.Array) -> jax.Array:
    """(128, 1) column of non-negative values → (1, 128) lane row."""
    return jnp.max(jnp.where(eye, jnp.broadcast_to(col, eye.shape), 0),
                   axis=0, keepdims=True)


def _digest_rows(x: jax.Array, g: int, ma_ref, ss_ref, eye) -> None:
    """Write (or, past the first column block, fold in) the digest of the
    128 value rows ``x`` into lane row ``g`` of the digest blocks."""
    xf = x.astype(jnp.float32)
    mx = _col_to_row(jnp.max(jnp.abs(xf), axis=1, keepdims=True), eye)
    sq = _col_to_row(jnp.sum(xf * xf, axis=1, keepdims=True), eye)
    first = pl.program_id(1) == 0
    ma_ref[g:g + 1, :] = jnp.where(first, mx,
                                   jnp.maximum(ma_ref[g:g + 1, :], mx))
    ss_ref[g:g + 1, :] = jnp.where(first, sq, ss_ref[g:g + 1, :] + sq)


def _join_kernel(av_ref, aver_ref, bv_ref, bver_ref, ov_ref, over_ref,
                 *digest_refs):
    a_ver = aver_ref[...]              # [bn / 128, 128]
    b_ver = bver_ref[...]
    over_ref[...] = jnp.maximum(a_ver, b_ver)
    take = (b_ver > a_ver).astype(jnp.int32)
    eye = _eye()
    for g in range(take.shape[0]):
        rows = pl.ds(g * LANES, LANES)
        pick = _row_to_col(take[g:g + 1, :], eye) > 0
        merged = jnp.where(pick, bv_ref[rows, :], av_ref[rows, :])
        ov_ref[rows, :] = merged
        if digest_refs:
            _digest_rows(merged, g, *digest_refs, eye)


def _join_call(a_vals, a_vers, b_vals, b_vers, block_n, interpret,
               digest: bool):
    n, chunk = a_vals.shape
    bn, np_, cw = _blocks(n, chunk, a_vals.dtype.itemsize, block_n)
    pad = np_ - n
    a_vals, a_vers, b_vals, b_vers = (
        _pad_rows(x, pad) for x in (a_vals, a_vers, b_vals, b_vers))
    vspec = pl.BlockSpec((bn, cw), lambda i, j: (i, j))
    cspec = pl.BlockSpec((bn // LANES, LANES), lambda i, j: (i, 0))
    col = (np_ // LANES, LANES)
    out_shape = [jax.ShapeDtypeStruct((np_, chunk), a_vals.dtype),
                 jax.ShapeDtypeStruct(col, a_vers.dtype)]
    if digest:
        out_shape += [jax.ShapeDtypeStruct(col, jnp.float32)] * 2
    outs = pl.pallas_call(
        _join_kernel,
        grid=(np_ // bn, chunk // cw),
        in_specs=[vspec, cspec, vspec, cspec],
        out_specs=[vspec] + [cspec] * (len(out_shape) - 1),
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(a_vals, _col_view(a_vers), b_vals, _col_view(b_vers))
    return tuple(o[:n] if k == 0 else o.reshape(-1)[:n]
                 for k, o in enumerate(outs))


def delta_join(a_vals: jax.Array, a_vers: jax.Array,
               b_vals: jax.Array, b_vers: jax.Array,
               block_n: int = ROW_TILE,
               interpret: bool = False) -> Tuple[jax.Array, jax.Array]:
    """a_vals, b_vals [n, chunk]; a_vers, b_vers [n] int32.

    ``n`` need not be a multiple of the block size: ragged chunk counts
    are zero-padded to the block boundary (⊥ versions) and sliced back.
    """
    return _join_call(a_vals, a_vers, b_vals, b_vers, block_n, interpret,
                      digest=False)


def batched_delta_join(segments: Sequence[Tuple[jax.Array, jax.Array,
                                                jax.Array, jax.Array]],
                       block_n: int = ROW_TILE, interpret: bool = False,
                       join_fn=None, host_stage: bool = False,
                       host_join_fn=None
                       ) -> List[Tuple[jax.Array, jax.Array]]:
    """Join many independent versioned-chunk segments in as few kernel
    launches as possible.

    ``segments`` is a sequence of ``(a_vals, a_vers, b_vals, b_vers)``
    tuples (each ``[n_s, chunk_s]`` / ``[n_s]``). Segments sharing a
    (chunk width, value dtype, version dtype) signature are concatenated
    along the chunk axis into ONE stacked launch — the merge is pointwise
    per chunk, so stacking chunks from many ``TensorState`` objects is
    exact — and the outputs are split back per segment. This replaces one
    jit dispatch *per object* with one launch *per signature*, which is
    the objects/sec win for keyed stores holding thousands of tensors.

    ``host_stage=True`` routes the glue through host numpy — near
    zero-copy for CPU-backed arrays, where ``jnp.concatenate`` over
    thousands of operands dominates — runs ONE single-grid-step launch
    per signature (``host_join_fn(a_vals, a_vers, b_vals, b_vers, rows)``,
    default: :func:`delta_join` with ``block_n=rows``) and returns the
    per-segment outputs as numpy views into the stacked result. Use on
    CPU; keep the default on-device path on TPU.

    ``join_fn`` overrides the two-operand join of the on-device path
    (e.g. the jit'd wrapper in ``kernels.ops``); defaults to
    :func:`delta_join`. Returns ``(out_vals, out_vers)`` per segment, in
    input order.
    """
    import numpy as np

    if join_fn is None:
        join_fn = functools.partial(delta_join, block_n=block_n,
                                    interpret=interpret)
    if host_join_fn is None:
        host_join_fn = lambda av, avr, bv, bvr, rows: delta_join(
            av, avr, bv, bvr, block_n=rows, interpret=interpret)
    results: List[Tuple[jax.Array, jax.Array]] = [None] * len(segments)
    groups = {}
    for i, (av, avr, bv, bvr) in enumerate(segments):
        sig = (av.shape[1], jnp.dtype(av.dtype), jnp.dtype(avr.dtype))
        groups.setdefault(sig, []).append(i)
    for sig, idxs in groups.items():
        if len(idxs) == 1 and not host_stage:
            results[idxs[0]] = join_fn(*segments[idxs[0]])
            continue
        sizes = [segments[i][0].shape[0] for i in idxs]
        if host_stage:
            cat = [np.concatenate([np.asarray(segments[i][j])
                                   for i in idxs], axis=0)
                   for j in range(4)]
            ov, over = host_join_fn(*cat, cat[0].shape[0])
            ov, over = np.asarray(ov), np.asarray(over)
        else:
            cat = [jnp.concatenate([segments[i][j] for i in idxs], axis=0)
                   for j in range(4)]
            ov, over = join_fn(*cat)
        start = 0
        for i, n_s in zip(idxs, sizes):
            results[i] = (ov[start:start + n_s], over[start:start + n_s])
            start += n_s
    return results


def fused_join_digest(a_vals: jax.Array, a_vers: jax.Array,
                      b_vals: jax.Array, b_vers: jax.Array,
                      block_n: int = ROW_TILE, interpret: bool = False
                      ) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """:func:`delta_join` and :func:`chunk_digest` of the merged result in
    ONE pass over HBM: ``(out_vals, out_vers, max|out| per chunk,
    Σout² per chunk)``. The anti-entropy hot loop needs the digest of the
    state it just joined (to pick the next delta's chunks), and the merged
    tile is already in VMEM — a separate digest launch would re-read the
    whole store from HBM for two scalars per row. Ragged ``n`` is
    zero-padded (⊥ versions ⇒ zero digest) and sliced back."""
    return _join_call(a_vals, a_vers, b_vals, b_vers, block_n, interpret,
                      digest=True)


def _digest_kernel(x_ref, ma_ref, ss_ref):
    eye = _eye()
    for g in range(ma_ref.shape[0]):
        _digest_rows(x_ref[pl.ds(g * LANES, LANES), :], g, ma_ref, ss_ref,
                     eye)


def chunk_digest(x: jax.Array, block_n: int = ROW_TILE,
                 interpret: bool = False) -> Tuple[jax.Array, jax.Array]:
    """x [n, chunk] → (max|x| per chunk [n], Σx² per chunk [n]).
    Ragged ``n`` is zero-padded to the block boundary and sliced back."""
    n, chunk = x.shape
    bn, np_, cw = _blocks(n, chunk, x.dtype.itemsize, block_n)
    x = _pad_rows(x, np_ - n)
    cspec = pl.BlockSpec((bn // LANES, LANES), lambda i, j: (i, 0))
    col = jax.ShapeDtypeStruct((np_ // LANES, LANES), jnp.float32)
    ma, ss = pl.pallas_call(
        _digest_kernel,
        grid=(np_ // bn, chunk // cw),
        in_specs=[pl.BlockSpec((bn, cw), lambda i, j: (i, j))],
        out_specs=[cspec, cspec],
        out_shape=[col, col],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(x)
    return ma.reshape(-1)[:n], ss.reshape(-1)[:n]


def _scatter_join_kernel(idx_ref, dver_ref, dv_ref, av_ref, aver_ref,
                         ama_ref, ass_ref, ov_ref, over_ref, oma_ref,
                         oss_ref):
    i = pl.program_id(0)
    row = idx_ref[i]
    prev = idx_ref[jnp.maximum(i - 1, 0)]
    # rows arrive sorted, so the delta rows landing in one block are
    # consecutive grid steps and the block stays in VMEM between them:
    # its first step copies the resident block in, later steps merge into
    # what earlier steps wrote
    @pl.when((i == 0) | (prev // 8 != row // 8))
    def _load_values():
        ov_ref[...] = av_ref[...]

    @pl.when((i == 0) | (prev // ROW_TILE != row // ROW_TILE))
    def _load_columns():
        over_ref[...] = aver_ref[...]
        oma_ref[...] = ama_ref[...]
        oss_ref[...] = ass_ref[...]

    vrow = pl.ds(row % 8, 1)
    crow = pl.ds((row % ROW_TILE) // LANES, 1)
    hit = jax.lax.broadcasted_iota(jnp.int32, (1, LANES), 1) == row % LANES
    vers = over_ref[crow, :]                                    # [1, 128]
    a_ver = jnp.max(jnp.where(hit, vers, jnp.iinfo(vers.dtype).min),
                    axis=1, keepdims=True)                      # [1, 1]
    b_ver = dver_ref[i]
    take = b_ver > a_ver
    merged = jnp.where(take, dv_ref[pl.ds(i % 8, 1), :], ov_ref[vrow, :])
    ov_ref[vrow, :] = merged
    over_ref[crow, :] = jnp.where(hit, jnp.maximum(a_ver, b_ver), vers)
    mf = merged.astype(jnp.float32)
    fresh = hit & take
    oma_ref[crow, :] = jnp.where(
        fresh, jnp.max(jnp.abs(mf), axis=1, keepdims=True), oma_ref[crow, :])
    oss_ref[crow, :] = jnp.where(
        fresh, jnp.sum(mf * mf, axis=1, keepdims=True), oss_ref[crow, :])


def _scatter_launch(vals, vers, maxabs, sumsq, idx, d_vals, d_vers,
                    interpret):
    chunk = vals.shape[1]
    tile = lambda i, idx, dver: (idx[i] // ROW_TILE, 0)
    vspec = pl.BlockSpec((8, chunk), lambda i, idx, dver: (idx[i] // 8, 0))
    cspec = pl.BlockSpec((8, LANES), tile)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(int(idx.shape[0]),),
        in_specs=[pl.BlockSpec((8, chunk), lambda i, idx, dver: (i // 8, 0)),
                  vspec, cspec, cspec, cspec],
        out_specs=[vspec, cspec, cspec, cspec],
    )
    return pl.pallas_call(
        _scatter_join_kernel,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype)
                   for x in (vals, vers, maxabs, sumsq)],
        # operand order counts the prefetched idx and d_vers as inputs 0
        # and 1: vals=3, vers=4, maxabs=5, sumsq=6 alias onto the four
        # outputs so blocks no grid step covers keep their resident values
        input_output_aliases={3: 0, 4: 1, 5: 2, 6: 3},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(idx, d_vers, d_vals, vals, vers, maxabs, sumsq)


def scatter_join(vals: jax.Array, vers: jax.Array,
                 maxabs: jax.Array, sumsq: jax.Array,
                 idx: jax.Array, d_vals: jax.Array, d_vers: jax.Array,
                 interpret: bool = False
                 ) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Scatter-merge ``r`` sparse delta rows into resident columns and
    refresh the touched rows' digest, all in ONE launch.

    ``vals [n, chunk]`` / ``vers [n]`` are the resident stacked columns,
    ``maxabs`` / ``sumsq`` ``[n] f32`` their per-chunk digest columns;
    ``idx [r] int32`` are the target row positions and ``d_vals [r,
    chunk]`` / ``d_vers [r]`` the shipped rows. The rows are sorted by
    position and the grid walks them — the prefetched ``idx`` drives the
    resident block index maps, so the kernel touches O(r) blocks of state
    no matter how large the store is — and ``input_output_aliases``
    carries every untouched block through unchanged (on TPU the update
    happens in the resident buffers; no O(n) copy when ``n`` is a
    multiple of ``ROW_TILE``). Rows that share a block merge in turn, so
    a repeated position is merged once per occurrence; the XLA oracle
    leaves the winner of a repeat unspecified, so callers repeat a
    position only with content that merges to the same row (the pad-row
    convention: ⊥-versioned pad rows re-write a row's existing content).
    A row's digest is refreshed when its delta row wins."""
    n = vals.shape[0]
    r = int(idx.shape[0])
    if r == 0:
        return vals, vers, maxabs, sumsq
    order = jnp.argsort(idx)
    idx, d_vals, d_vers = idx[order], d_vals[order], d_vers[order]
    pad_r = (-r) % 8
    if pad_r:        # whole 8-row delta blocks: ⊥ rows re-merge the last row
        idx = jnp.concatenate([idx, jnp.full((pad_r,), idx[-1], idx.dtype)])
        d_vals = _pad_rows(d_vals, pad_r)
        d_vers = _pad_rows(d_vers, pad_r, jnp.iinfo(d_vers.dtype).min)
    pad_n = padded_rows(n) - n
    cols = [_pad_rows(vals, pad_n)] + [_col_view(_pad_rows(x, pad_n))
                                       for x in (vers, maxabs, sumsq)]
    for s in range(0, r + pad_r, _SCATTER_ROWS):
        e = s + _SCATTER_ROWS
        cols = _scatter_launch(*cols, idx[s:e], d_vals[s:e], d_vers[s:e],
                               interpret)
    out_vals, *rest = cols
    return (out_vals[:n],) + tuple(x.reshape(-1)[:n] for x in rest)
