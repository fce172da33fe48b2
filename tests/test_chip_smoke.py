"""``chip_smoke.py`` off the chip: its phases at toy scale on the CPU
(the XLA oracle paths stand in for the Mosaic kernels), and its refusal
to run anywhere but on a TPU."""

import importlib.util
import os
from types import SimpleNamespace

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(ROOT, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_main_refuses_a_host_without_tpu(smoke, capsys):
    with pytest.raises(SystemExit) as e:
        smoke.main([])
    assert e.value.code not in (0, None)
    assert "no TPU" in str(e.value.code)
    assert '"ok"' not in capsys.readouterr().out


def test_store_phase_converges_to_the_reference(smoke):
    from repro.kernels import ops
    before = dict(ops.counters.by_kernel)
    out = smoke.store_phase(5, n_keys=1024, rounds=3, dots_per_rid=5000)
    assert out["replicas"] == 3 and out["rows_written"] > 0
    assert out["resident_bytes_per_replica"][0] >= 1024 * 16 * 256 * 4
    assert ops.counters.by_kernel.get("scatter_join:xla", 0) \
        > before.get("scatter_join:xla", 0)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_train_phase_matches_the_outer_reference(smoke, monkeypatch, dtype):
    """bf16 params (the published configs') round every partial sum of
    the outer update: the check's tolerance must cover exactly that."""
    import dataclasses
    from repro import configs
    from repro.launch import train
    cfg = dataclasses.replace(configs.get_config("qwen1.5-0.5b", True),
                              dtype=dtype)
    for mod in (configs, train):
        monkeypatch.setattr(mod, "get_config", lambda arch, reduced: cfg)
    args = SimpleNamespace(
        arch="qwen1.5-0.5b", reduced=True, pods=2, steps=4, local_steps=2,
        batch=4, seq=32, lr=3e-4, seed=0, net_loss=0.2, topk=None,
        ship_policy="all")
    assert smoke.plan_training(args, 1 << 40, log=lambda s: None) == []
    out = smoke.train_phase(args)
    assert out["dots"] == 4 and len(out["losses"]) == 8


def test_plan_training_cuts_rounds_then_sequence(smoke):
    args = SimpleNamespace(
        arch="qwen1.5-0.5b", reduced=True, pods=2, steps=4, local_steps=2,
        batch=2, seq=256, lr=3e-4, seed=0)
    from repro.configs import get_config
    step, one = smoke._step_bytes(get_config(args.arch, reduced=True), args)
    cuts = smoke.plan_training(args, step + 3 * one - 1, log=lambda s: None)
    assert cuts[0] == "outer rounds 2 -> 1"
    assert cuts[1:] and all(c.startswith("seq ") for c in cuts[1:])
    assert args.steps == args.local_steps and args.seq < 256
