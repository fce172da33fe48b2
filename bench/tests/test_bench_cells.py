"""Both cells at test sizes on the CPU, through their drivers without the
harness's look for a chip: a sound run is correct, and each fault the
cell can have, and its control, planted under its timed path, makes it
incorrect."""

import numpy as np
import pytest

from bench import faults
from bench.tests.helpers import run_small, small_cell

STORE, TRAIN = "ycsb_a-3rep", "delta2-qwen1.5-0.5b"


def _store_cell():
    cell = small_cell(STORE)
    cell.traffic["drain_limit_s"] = 1.0
    return cell


def test_store_run_converges_to_the_reference():
    line, rec = run_small(STORE, 2**31 + 5, 1.0, _store_cell())
    assert line["correct"], line["checks"]
    assert line["attempted"] == 40 and line["failed"] == 0
    assert set(line["checks"]) == {"rows_apart", "rows_wrong", "reads_wrong",
                                   "updates_lost"}
    m = line["metrics"]
    assert {"update_visible_p50_ms", "setup_s"} == set(m)
    assert rec.counts["join_bytes"] > 0 and rec.spans["tick"]
    assert len(rec.samples["update_visible_ms"]) == 20
    assert len(rec.samples["read_ms"]) == 20


def test_store_base_is_a_function_of_the_seed():
    from bench.drivers.store import make_base
    a, b = make_base(2**33 + 1, 64, 8), make_base(2**33 + 1, 64, 8)
    assert np.array_equal(a, b) and a.dtype == np.float32
    assert not np.array_equal(a, make_base(2**33 + 2, 64, 8))


@pytest.mark.parametrize("fault", sorted(faults.STORE))
def test_a_store_fault_makes_the_run_incorrect(fault):
    cell = _store_cell()
    with faults.STORE[fault](cell):
        line, _ = run_small(STORE, 17, 1.0, cell)
    assert not line["correct"], line["checks"]


def test_train_run_matches_the_reference():
    line, rec = run_small(TRAIN, 2**31 + 11, 1.0)
    assert line["correct"], (line["checks"], rec.info)
    assert line["attempted"] > 0 and line["failed"] == 0
    assert {"tokens_per_s", "setup_s"} == set(line["metrics"])
    assert {"first_loss_gap", "loss_gap", "grad_norm_gap", "change_norm_gap",
            "outer_error_over_tolerance", "pods_apart", "jobs_unconverged",
            "nonfinite_losses"} == set(line["checks"])


@pytest.mark.parametrize("fault", sorted(faults.TRAIN))
def test_a_training_fault_makes_the_run_incorrect(fault):
    cell = small_cell(TRAIN)
    with faults.TRAIN[fault](cell):
        line, rec = run_small(TRAIN, 23, 0.5, cell)
    assert not line["correct"], (line["checks"], rec.info)

