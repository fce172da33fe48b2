"""Work a configuration needs, counted from its shapes.

These counts are the yardstick of every roofline share and utilization
the benchmark reports: the operations and bytes the algorithm needs,
not what the current code happens to move. They read only the
configuration file's sizes.
"""

from __future__ import annotations


def join_row_bytes(record_floats: int, value_bytes: int = 4,
                   version_bytes: int = 4, digest_bytes: int = 8) -> int:
    """Bytes one versioned-chunk row costs a scatter join: the delta row
    read (values and version), the touched resident row read and written
    back (values and version), and its digest row (max|x|, sum x^2)
    written."""
    row = record_floats * value_bytes + version_bytes
    return 3 * row + digest_bytes


def scatter_join_bytes(rows: int, record_floats: int) -> int:
    """Bytes a scatter join of ``rows`` delta rows needs."""
    return rows * join_row_bytes(record_floats)


def dense_lm_matmul_params(model: dict) -> int:
    """Parameters that take part in a matrix multiplication per token
    of a dense decoder: the attention and gated MLP projections of every
    layer and the output head (tied or not). Norms, biases and the
    embedding gather are not matrix work."""
    d = model["hidden_size"]
    heads = model["num_attention_heads"]
    kv = model["num_key_value_heads"]
    hd = d // heads
    ff = model["intermediate_size"]
    per_layer = d * heads * hd + 2 * d * kv * hd + heads * hd * d + 3 * d * ff
    return model["num_hidden_layers"] * per_layer + model["vocab_size"] * d


def dense_lm_train_flops_per_token(model: dict, seq: int) -> float:
    """Operations a training step needs per token: forward and backward
    (three times the forward) of every matrix multiplication, plus causal
    attention's scores and weighted sum over the (seq + 1) / 2 keys a
    token sees on average. Recomputation is not counted."""
    d = model["hidden_size"]
    mm = 2 * dense_lm_matmul_params(model)
    attn = model["num_hidden_layers"] * 2 * 2 * d * (seq + 1) / 2
    return 3 * (mm + attn)
