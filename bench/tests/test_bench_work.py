"""Work counts from shapes, and the peaks table."""

import pytest

from bench import work
from bench.peaks import peaks

QWEN = {"hidden_size": 1024, "intermediate_size": 2816,
        "num_attention_heads": 16, "num_key_value_heads": 16,
        "num_hidden_layers": 24, "vocab_size": 151936}


def test_join_bytes_per_row_by_hand():
    # delta row in, resident row read and written: 3 x (256 x 4 + 4);
    # digest row written: 8
    assert work.join_row_bytes(256) == 3 * 1028 + 8 == 3092
    assert work.scatter_join_bytes(10, 256) == 30920


def test_matmul_params_of_qwen1_5_0_5b_by_hand():
    layer = (1024 * 1024 * 4) + 3 * 1024 * 2816        # q k v o + gate up down
    assert work.dense_lm_matmul_params(QWEN) == 24 * layer + 151936 * 1024
    # with q/k/v biases (bf16) and norm scales (f32) the parameters take
    # the 928,075,776 B of one params copy
    biases, norms = 24 * 3 * 1024, 24 * 2 * 1024 + 1024
    assert 2 * (work.dense_lm_matmul_params(QWEN) + biases) + 4 * norms \
        == 928_075_776


def test_train_flops_per_token_by_hand():
    n = work.dense_lm_matmul_params(QWEN)
    attn = 24 * 2 * 2 * 1024 * (512 + 1) / 2
    assert work.dense_lm_train_flops_per_token(QWEN, 512) == \
        pytest.approx(3 * (2 * n + attn))
    # about 6 N per token, plus attention
    per_step = work.dense_lm_train_flops_per_token(QWEN, 512) * 4096
    assert 1.1e13 < per_step < 1.2e13


def test_gqa_counts_smaller_kv_projections():
    gqa = dict(QWEN, num_key_value_heads=4)
    d, hd = 1024, 64
    saved = 24 * 2 * d * (16 - 4) * hd
    assert work.dense_lm_matmul_params(QWEN) \
        - work.dense_lm_matmul_params(gqa) == saved


def test_peaks_of_the_v5e_and_refusal_of_unknown_devices():
    p = peaks("TPU v5 lite")
    assert p["bf16_flops_s"] == 197e12 and p["hbm_bytes_s"] == 819e9
    assert "Google Cloud" in p["source"]
    with pytest.raises(KeyError, match="no published peaks"):
        peaks("TPU v99 imaginary")
