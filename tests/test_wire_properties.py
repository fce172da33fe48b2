"""Property tests (hypothesis) for the binary δ-wire codec:

* ``decode(encode(x)) == x`` over random stores mixing lattice types
  (tensor states with ragged chunk counts / random sparsity / several
  dtypes, counters, OR-Sets, empty deltas);
* joining the decoded (sparse, zero-copy) store into random resident
  state equals joining the original — the ingest-path faithfulness the
  engine relies on;
* random frame corruption never decodes silently: every flipped byte is
  either detected (FrameError) or harmless (decodes equal).
"""

import pytest
import pytest as _pytest
_pytest.importorskip(
    "hypothesis", reason="dev dependency — pip install -r requirements-dev.txt")
from hypothesis import example, given, settings, strategies as st

import numpy as np

from repro.core import AWORSet, GCounter, LatticeStore
from repro.core.tensor_lattice import (ChunkedTensor, TensorState,
                                       sparse_chunks)
from repro.wire import (FrameError, decode_frame, decode_store,
                        encode_frame, encode_store)

DTYPES = (np.float32, np.float16, np.int32)


@st.composite
def tensor_states(draw):
    rng = np.random.default_rng(draw(st.integers(0, 2**31 - 1)))
    n_tensors = draw(st.integers(0, 3))
    chunks = {}
    for t in range(n_tensors):
        n_chunks = draw(st.integers(1, 7))          # ragged across tensors
        chunk = draw(st.sampled_from((4, 8, 16)))
        dtype = draw(st.sampled_from(DTYPES))
        if np.issubdtype(dtype, np.floating):
            vals = rng.normal(size=(n_chunks, chunk)).astype(dtype)
        else:
            vals = rng.integers(-50, 50,
                                size=(n_chunks, chunk)).astype(dtype)
        vers = rng.integers(0, 5, size=(n_chunks,)).astype(np.int32)
        vals[vers == 0] = 0                          # ⊥ invariant
        if draw(st.booleans()):                      # sparse-form value
            live = np.nonzero(vers > 0)[0]
            chunks[f"t{t}"] = sparse_chunks(
                n_chunks, live.astype(np.int32), vals[live], vers[live])
        else:
            chunks[f"t{t}"] = ChunkedTensor(vals, vers)
    return TensorState.of(chunks, lamport=draw(st.integers(0, 9)))


@st.composite
def stores(draw):
    out = {}
    for k in range(draw(st.integers(0, 5))):
        kind = draw(st.sampled_from(("tensor", "counter", "orset",
                                     "empty")))
        key = f"key{k}"
        if kind == "tensor":
            out[key] = draw(tensor_states())
        elif kind == "counter":
            c = GCounter.bottom()
            for r in range(draw(st.integers(1, 3))):
                c = c.join(c.inc_delta(f"r{r}"))
            out[key] = c
        elif kind == "orset":
            s = AWORSet.bottom()
            for e in range(draw(st.integers(1, 3))):
                s = s.join(s.add_delta("r0", f"e{e}"))
            out[key] = s
        else:
            out[key] = TensorState.bottom()
    return LatticeStore.of(out)


@settings(max_examples=40, deadline=None)
@given(store=stores())
def test_decode_encode_is_identity(store):
    dec = decode_store(encode_store(store))
    assert dec == store
    assert dec.leq(store) and store.leq(dec)


def _one_tensor(n_chunks, width, dtype=np.float32, version=1,
                sparse=False, extra=None):
    """A one-key store whose tensor ``t0`` holds ``n_chunks`` rows of
    ``width`` at ``version``; ``extra`` adds keys beside it."""
    vals = np.arange(n_chunks * width).reshape(n_chunks, width).astype(dtype)
    vers = np.full((n_chunks,), version, np.int32)
    ct = (sparse_chunks(n_chunks, np.arange(n_chunks, dtype=np.int32), vals,
                        vers) if sparse else ChunkedTensor(vals, vers))
    return LatticeStore.of({"key0": TensorState.of({"t0": ct}),
                            **(extra or {})})


@settings(max_examples=25, deadline=None)
@given(resident=stores(), delta=stores())
# the same tensor name at two layouts or dtypes: once the dense join
# broadcast while the decoded (sparse) delta's join raised or differed
@example(resident=_one_tensor(1, 4, sparse=True),
         delta=_one_tensor(1, 8, version=3))
@example(resident=_one_tensor(2, 4, version=3,
                              extra={"key1": GCounter.bottom()}),
         delta=_one_tensor(1, 4, np.int32, version=3))
@example(resident=_one_tensor(1, 4, version=0),
         delta=_one_tensor(2, 4, version=2))
def test_decoded_store_joins_identically(resident, delta):
    dec = decode_store(encode_store(delta))
    try:
        want = resident.join(delta)
    except Exception:
        # key-type mismatch between the two random stores (joining a
        # counter into a tensor key, or a tensor into one of another
        # layout, is a type error with or without the codec) — not a
        # wire property
        return
    assert resident.join(dec) == want


@settings(max_examples=40, deadline=None)
@given(store=stores(), flip=st.integers(0, 2**31 - 1),
       bit=st.integers(0, 7))
def test_corrupted_frames_never_decode_silently_wrong(store, flip, bit):
    frame = encode_frame("delta", encode_store(store))
    pos = flip % len(frame)
    corrupt = bytearray(frame)
    corrupt[pos] ^= 1 << bit
    if bytes(corrupt) == bytes(frame):
        return
    try:
        kind, payload = decode_frame(bytes(corrupt))
        dec = decode_store(payload)
    except Exception:
        return                      # rejected — the expected outcome
    # a flip that survives validation must not change the content (the
    # CRC covers header AND payload, so every single-bit flip should in
    # fact be rejected — this branch documents the safety property)
    assert kind == "delta" and dec == store
