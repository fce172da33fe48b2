"""The harness finds a cell's parts by name, the generator is a function
of the seed, and the command refuses a host without a TPU."""

import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

from bench import harness, workgen
from bench.record import Record

ROOT = harness.ROOT


def test_every_cell_of_the_benchmark_resolves():
    bench = harness.load_benchmark()
    for wl in bench["workloads"]:
        cell = harness.find_cell(bench, wl["name"])
        assert harness.driver(cell.config).run
        for m in cell.end_to_end + cell.per_layer:
            assert callable(harness.metric_reader(m["name"]))
        names = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in names and len(names) >= 2
        assert cell.per_layer


def test_files_dropped_in_are_found_without_edits(tmp_path):
    """A later PR adds a configuration, a traffic mix, a driver and a
    metric as new files and entries: the harness finds each by name."""
    root = tmp_path
    shutil.copytree(os.path.join(ROOT, "bench"), root / "bench")
    bench = harness.load_benchmark()
    (root / "bench" / "configs" / "toy.json").write_text(json.dumps(
        {"name": "toy", "driver": "toy_driver", "size": 3}))
    (root / "bench" / "traffic" / "toy_mix.json").write_text(json.dumps(
        {"rate_ops_s": 7}))
    (root / "bench" / "drivers" / "toy_driver.py").write_text(
        "def run(cell, seed, seconds, rec, devices):\n"
        "    rec.counts['toy'] = cell.config['size'] * "
        "cell.traffic['rate_ops_s']\n"
        "    return {}\n")
    (root / "bench" / "metrics" / "toy.rate-x.py").write_text(
        "def read(rec, ctx):\n    return rec.counts.get('toy')\n")
    bench["configs"].append({"name": "toy", "source": "x",
                             "file": "bench/configs/toy.json",
                             "reduced": [], "why": "x"})
    bench["workloads"].append({"name": "toy.cell", "config": "toy",
                               "traffic": "toy_mix", "chips": 1,
                               "why": "x"})
    bench["per_layer"].append({"name": "toy.rate-x", "unit": "1",
                               "better": "higher", "source": "host_clock",
                               "layer": "toy", "moves": "setup_s",
                               "workloads": ["toy.cell"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    cell = harness.find_cell(harness.load_benchmark(str(root)), "toy.cell",
                             str(root))
    assert cell.config["size"] == 3 and cell.traffic["rate_ops_s"] == 7
    assert [m["name"] for m in cell.per_layer] == ["toy.rate-x"]
    rec = Record(0.0, trace=True, log=lambda s: None)
    harness.driver(cell.config, str(root)).run(cell, 1, 1.0, rec, [])
    assert harness.read_metrics(cell.per_layer, rec, {}, str(root)) == \
        {"toy.rate-x": {"value": 21.0, "unit": "1"}}


def test_a_reader_with_nothing_to_read_leaves_its_metric_out():
    rec = Record(0.0, trace=False, log=lambda s: None)
    metrics = [{"name": "store.put_ms", "unit": "ms"},
               {"name": "store_join_roofline", "unit": "%"},
               {"name": "train.mfu", "unit": "%"}]
    assert harness.read_metrics(metrics, rec, {"peaks": {}}) == {}


def test_same_seed_same_operations_and_every_seed_the_same_work():
    traffic = harness.load_traffic("ycsb_a")
    a = workgen.open_loop(traffic, 4096, 3, 8, 2**31 + 99, 5.0, stream=2)
    b = workgen.open_loop(traffic, 4096, 3, 8, 2**31 + 99, 5.0, stream=2)
    c = workgen.open_loop(traffic, 4096, 3, 8, 12345, 5.0, stream=2)
    for f in ("due", "kind", "record", "client", "values"):
        assert np.array_equal(getattr(a, f), getattr(b, f))
    assert not np.array_equal(a.record, c.record)
    n = round(traffic["rate_ops_s"] * 5.0)
    assert len(a) == len(c) == n
    assert a.due[0] == 0.0 and a.due[-1] < 5.0
    # the same gaps between arrivals, the same split and spread, in
    # another order
    gaps = [np.sort(np.diff(np.r_[x.due, 5.0])) for x in (a, c)]
    assert np.allclose(*gaps) and not np.array_equal(a.due, c.due)
    assert not np.array_equal(a.kind, c.kind)
    assert (a.kind == workgen.UPDATE).sum() == (c.kind == workgen.UPDATE).sum()
    assert np.bincount(a.client).tolist() == np.bincount(c.client).tolist()
    t1 = workgen.token_batch(7, 0, 1, 2, 4, 16, 97)
    t2 = workgen.token_batch(7, 0, 1, 2, 4, 16, 97)
    t3 = workgen.token_batch(7, 0, 1, 3, 4, 16, 97)
    assert np.array_equal(t1["tokens"], t2["tokens"])
    assert np.array_equal(t1["tokens"][:, 1:], t1["labels"][:, :-1])
    assert not np.array_equal(t1["tokens"], t3["tokens"])


def test_zipf_draws_favour_few_records():
    rng = np.random.default_rng(0)
    rec = workgen.scrambled_zipf(rng, 10_000, 20_000, 0.99)
    top = np.sort(np.bincount(rec, minlength=10_000))[::-1]
    assert top[:100].sum() > 0.3 * rec.size


def test_the_command_refuses_a_host_without_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "bench", "run.py"),
         "--workload", "ycsb_a-3rep", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=ROOT, env=env, capture_output=True,
        text=True, timeout=120)
    assert out.returncode != 0
    assert out.stdout.strip() == ""
    assert "no TPU" in out.stderr


def test_the_command_refuses_a_checkout_without_the_program(tmp_path):
    """A directory holding only BENCHMARK.json and the benchmark's own
    files has no system under test."""
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    out = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "ycsb_a-3rep",
         "--seed", "1", "--seconds", "1", "--trace", "0"], cwd=tmp_path,
        env=env, capture_output=True, text=True, timeout=120)
    assert out.returncode != 0 and out.stdout.strip() == ""


@pytest.mark.parametrize("values,want", [([], None), ([5.0], 5.0),
                                         (list(range(1, 101)), 95),
                                         (list(range(1, 21)), 19)])
def test_percentile_is_nearest_rank(values, want):
    assert harness.percentile(values, 95) == want
