"""Launch-path integration: lower + compile train/prefill/decode steps on a
real (2×4) multi-device mesh with the full sharding machinery — the same
code path as the 512-device production dry-run, at test scale. Subprocess
keeps the fake devices out of the test session."""

import os
import subprocess
import sys
import textwrap

import pytest

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import dataclasses
    import jax, jax.numpy as jnp
    from repro.configs import get_config
    from repro.configs.shapes import ShapeCase
    from repro.dist import make_rules
    from repro.launch.dryrun import _cell_costs, _lower_and_compile
    from repro.launch.mesh import make_mesh

    arch = os.environ["TEST_ARCH"]
    step = os.environ["TEST_STEP"]
    cfg = get_config(arch, reduced=True)
    if os.environ.get("TEST_MOE_LOCAL") == "1":
        cfg = dataclasses.replace(cfg, moe_impl="local")
    seq = cfg.ssm.chunk * 2 if cfg.ssm is not None else 32
    if cfg.input_mode == "tokens+prefix":
        seq = max(seq, cfg.prefix_len + 16)
    case = ShapeCase("t", seq, 8, step)
    mesh = make_mesh((2, 4), ("data", "model"))
    rules = make_rules(mesh)
    lowered, compiled = _lower_and_compile(cfg, case, mesh, False, rules)
    costs = _cell_costs(compiled, 8)
    assert costs["flops"] > 0
    mem = compiled.memory_analysis()
    print("OK", costs["flops"], costs["wire"])
""")


def _run(arch, step, moe_local=False):
    env = dict(os.environ)
    env.update({"TEST_ARCH": arch, "TEST_STEP": step,
                "PYTHONPATH": "src",
                "TEST_MOE_LOCAL": "1" if moe_local else "0"})
    r = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                       capture_output=True, text=True, timeout=900,
                       cwd=os.path.dirname(os.path.dirname(__file__)))
    assert r.returncode == 0, r.stderr[-3000:]
    assert "OK" in r.stdout


@pytest.mark.parametrize("arch,step", [
    ("qwen2-1.5b", "train"),          # GQA + bias + tied embeddings
    ("gemma2-27b", "prefill"),        # alternating windows + softcaps
    ("mamba2-130m", "train"),         # SSD, no attention
    ("jamba-v0.1-52b", "decode"),     # hybrid caches (ssm + kv + moe)
    ("deepseek-v2-236b", "decode"),   # MLA latent cache
])
def test_lower_and_compile_small_mesh(arch, step):
    _run(arch, step)


def test_moe_local_lowers_on_mesh():
    _run("mixtral-8x22b", "train", moe_local=True)
