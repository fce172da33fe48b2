"""Small cells for CPU tests: the benchmark's own cells with their sizes
cut so a test run holds them, run through the driver without the
harness's look for a chip."""

from __future__ import annotations

import time

import jax

from bench import harness
from bench.record import Record

SMALL = {
    "ycsb_a-3rep": ({"keys": 64}, {"rate_ops_s": 40.0, "warm_seconds": 0.5,
                                   "warm_join_rows": 4}),
    "delta2-qwen1.5-0.5b": (
        {"hidden_size": 32, "intermediate_size": 64,
         "num_attention_heads": 4, "num_key_value_heads": 4,
         "num_hidden_layers": 2, "vocab_size": 97,
         # bf16 rounding weighs more at these widths than at the
         # published ones (a sound run reads about 1e-3 here)
         "limits": {"first_loss_gap": 0.005, "loss_gap": 0.005,
                    "grad_norm_gap": 0.02, "change_norm_gap": 0.02}},
        {"batch": 4, "seq": 8, "local_steps": 3,
         "reference_micro_batches": 2}),
}

PEAKS = {"bf16_flops_s": 197e12, "hbm_bytes_s": 819e9}


def small_cell(name: str):
    cell = harness.find_cell(harness.load_benchmark(), name)
    cfg, traffic = SMALL[name]
    cell.config.update(cfg)
    cell.traffic.update(traffic)
    return cell


def run_small(name: str, seed: int, seconds: float, cell=None):
    """``(result line, record)`` of one run of a small cell."""
    cell = cell or small_cell(name)
    rec = Record(time.perf_counter(), trace=False, log=lambda s: None)
    ctx = harness.driver(cell.config).run(cell, seed, seconds, rec,
                                          jax.devices())
    ctx.update(peaks=PEAKS, config=cell.config, traffic=cell.traffic)
    return harness.result_line(cell, rec, ctx, {}), rec
