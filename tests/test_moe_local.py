"""shard_map local-dispatch MoE == global-dispatch MoE (numerically), on a
real multi-device mesh. Runs in a subprocess so the 8 fake host devices
don't leak into the rest of the test session."""

import os
import subprocess
import sys
import textwrap

import pytest

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import dataclasses
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.models import ModelConfig, MoESpec
    from repro.models.moe import apply_moe, init_moe
    from repro.models.hints import activation_rules

    EP = bool(int(os.environ["TEST_EP"]))
    # EP regime: E=8 divisible by model=4; TP regime: E=3 (indivisible)
    E = 8 if EP else 3
    cfg = ModelConfig(name="t", family="moe", n_layers=1, d_model=32,
                      n_heads=2, n_kv_heads=2, d_ff=0, vocab=17,
                      moe=MoESpec(num_experts=E, top_k=2, expert_d_ff=64,
                                  num_shared_experts=1, shared_d_ff=32,
                                  capacity_factor=float(E)),  # dropless
                      dtype="float32", moe_impl="local")

    from repro.launch.mesh import make_mesh
    mesh = make_mesh((2, 4), ("data", "model"))
    rules = {"tokens": "data", "batch": "data"}
    p, _ = init_moe(cfg, jax.random.PRNGKey(0), jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 16, 32), jnp.float32)

    y_ref, aux_ref, _ = apply_moe(p, dataclasses.replace(cfg,
                                                         moe_impl="global"),
                                  x)

    with mesh, activation_rules(mesh, rules):
        xs = jax.device_put(x, NamedSharding(mesh, P("data", None, None)))
        y, aux, _ = jax.jit(lambda p, x: apply_moe(p, cfg, x))(p, xs)

    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                               rtol=2e-4, atol=2e-4)
    # aux differs by per-shard averaging of mean-probs; must be close on
    # iid data and exactly equal when data shards are balanced
    assert abs(float(aux) - float(aux_ref)) < 0.4, (aux, aux_ref)
    print("OK", float(aux), float(aux_ref))
""")


@pytest.mark.parametrize("ep", [1, 0], ids=["expert-parallel", "tensor-parallel"])
def test_local_moe_matches_global(ep):
    env = dict(os.environ)
    env["TEST_EP"] = str(ep)
    env["PYTHONPATH"] = "src"
    r = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                       capture_output=True, text=True, timeout=600,
                       cwd=os.path.dirname(os.path.dirname(__file__)))
    assert r.returncode == 0, r.stderr[-3000:]
    assert "OK" in r.stdout
