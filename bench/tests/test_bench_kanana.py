"""The MoE training cell at test sizes on the CPU, through its driver
without the harness's look for a chip: a sound run is correct, and each
fault of its routing or held-expert layer, and its float8 control,
planted under the timed path, makes it incorrect."""

import pytest

from bench import faults_moe, harness
from bench.tests.helpers import run_small

CELL = "delta2-kanana2-30b-a3b-8k"
# the cell's MoE layer and routing at test widths: one MoE layer (the
# dense layer's path is held to the reference in tests/test_moe_held.py),
# 4 of a router's 16 experts held (not the first four), top-6; the widths
# make most matrices 32 x 32, so fewer shapes compile
SMALL = ({"hidden_size": 32, "num_attention_heads": 2,
          "num_key_value_heads": 2, "kv_lora_rank": 24,
          "qk_nope_head_dim": 8, "qk_rope_head_dim": 8, "v_head_dim": 8,
          "moe_intermediate_size": 16, "num_hidden_layers": 1,
          "first_k_dense_replace": 0,
          "router_experts": 16, "n_routed_experts": 4,
          "held_experts": [4, 4], "vocab_size": 97,
          # bf16 rounding weighs more at these widths than at the
          # published ones: sound runs (6 seeds) read change_norm_gap up to
          # 0.0076 and load_gap up to 0.0104 (one token's choice); at
          # seed 29 each fault exceeds a limit by 1.36x or more
          # (capacity_drops change_norm_gap 0.027, bias_ignored load_gap
          # 0.042, control_fp8 0.033 and 0.042)
          "limits": {"change_norm_gap": 0.02, "load_gap": 0.025}},
         {"batch": 2, "seq": 16, "local_steps": 3,
          "reference_micro_batches": 2})


def small_cell():
    cell = harness.find_cell(harness.load_benchmark(), CELL)
    cfg, traffic = SMALL
    cell.config.update(cfg)
    cell.traffic.update(traffic)
    return cell


def test_moe_train_run_matches_the_reference():
    line, rec = run_small(CELL, 2**31 + 13, 0.2, small_cell())
    assert line["correct"], (line["checks"], rec.info)
    assert line["attempted"] > 0 and line["failed"] == 0
    assert {"tokens_per_s", "setup_s"} == set(line["metrics"])
    assert {"change_norm_gap", "load_gap", "outer_error_over_tolerance",
            "pods_apart", "jobs_unconverged",
            "nonfinite_losses"} == set(line["checks"])
    assert {"first_loss_gap", "loss_gap", "grad_norm_gap"} \
        <= set(rec.info["gaps"])
    rows = rec.info["moe_rows"]
    assert rows.shape[1:] == (1, 4) and rows.sum() > 0
    assert rec.counts["gmm_flops_in_window"] > 0
    assert harness.metric_reader("moe.load_max_over_mean")(rec, {}) >= 1.0
    # the roofline reads the device trace, which an untraced run has not
    assert harness.metric_reader("moe.gmm_roofline")(rec, {}) is None
    assert min(rec.info["bias_moved"]) > 0.1


@pytest.mark.parametrize("fault", sorted(faults_moe.MOE))
def test_a_moe_fault_makes_the_run_incorrect(fault):
    cell = small_cell()
    with faults_moe.MOE[fault](cell):
        line, rec = run_small(CELL, 29, 0.2, cell)
    assert not line["correct"], (line["checks"], rec.info)
