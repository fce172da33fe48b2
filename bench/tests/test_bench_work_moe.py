"""Work counts of the MoE training cell's chip share, against hand counts
at a tiny shape, and at the cell's own shapes."""

import json
import os

import pytest

from bench import harness, work_moe

TINY = {"hidden_size": 8, "num_attention_heads": 2, "qk_nope_head_dim": 3,
        "qk_rope_head_dim": 1, "v_head_dim": 2, "kv_lora_rank": 4,
        "intermediate_size": 10, "moe_intermediate_size": 5,
        "num_hidden_layers": 3, "first_k_dense_replace": 1,
        "router_experts": 16, "held_experts": [4, 4],
        "num_experts_per_tok": 2, "n_shared_experts": 2, "vocab_size": 7}


def test_flops_per_token_by_hand_at_a_tiny_shape():
    # q 8x2x4, kv latent + rope key 8x(4+1), keys and values 4x2x(3+2),
    # output 2x2x8
    mla = 64 + 40 + 40 + 32
    assert work_moe.mla_matmul_params(TINY) == mla
    # router 8x16, shared 3x8x(2x5), routed 2 x 4/16 = 0.5 experts of
    # 3x8x5
    moe = 128 + 240 + 0.5 * 120
    assert work_moe.moe_matmul_params_per_token(TINY) == moe
    mm = 3 * mla + 1 * 3 * 8 * 10 + 2 * moe + 7 * 8
    # attention: 2 heads x (3 + 1 + 2) per key, over (9 + 1) / 2 keys
    attn = 3 * 2 * 12 * 5
    assert work_moe.train_flops_per_token(TINY, 9) == 3 * (2 * mm + attn)
    # one routed row: gate, up and down of 8x5, forward 2 flops each,
    # backward twice that
    assert work_moe.gmm_flops_per_row(TINY) == 3 * 3 * 2 * 8 * 5 == 720


def test_the_cells_count_is_the_sized_2_75_gflop_per_token():
    cell = harness.find_cell(harness.load_benchmark(),
                             "delta2-kanana2-30b-a3b-8k")
    cfg, seq = cell.config, cell.traffic["seq"]
    assert seq == 8192
    per_token = work_moe.train_flops_per_token(cfg, seq)
    assert per_token == pytest.approx(2.75e9, rel=0.01)
    # MLA's scores and weighted sum, about 84 MFLOP a token and layer
    # forward, against about 75 MFLOP of a MoE layer's projections
    attn = 2 * 32 * (128 + 64 + 128) * (seq + 1) / 2
    assert attn == pytest.approx(84e6, rel=0.01)
    proj = 2 * (work_moe.mla_matmul_params(cfg)
                + work_moe.moe_matmul_params_per_token(cfg))
    assert proj == pytest.approx(75.6e6, rel=0.01)
    assert work_moe.gmm_flops_per_row(cfg) == 18 * 2048 * 768


def test_the_cells_config_holds_every_published_key():
    """Every key of the published config is in the cell's file, changed
    only where ``BENCHMARK.json`` lists it as reduced."""
    bench = harness.load_benchmark()
    entry = {c["name"]: c for c in bench["configs"]}[
        "kanana-2-30b-a3b-ep16-2pod"]
    with open(os.path.join(harness.ROOT, entry["file"])) as f:
        cfg = json.load(f)
    published = {"num_hidden_layers": 48, "n_routed_experts": 128,
                 "vocab_size": 128256}
    for key, value in published.items():
        assert key in entry["reduced"] and cfg[key] != value
    assert cfg["router_experts"] == 128
    assert cfg["held_experts"][1] == cfg["n_routed_experts"] == 8
    assert cfg["vocab_size"] * 8 >= 128256
