"""The persistent compilation cache goes where ``JAX_COMPILATION_CACHE_DIR``
says, else to a fixed ``.jax_cache/`` at the repository root, and nothing
turns it on at import."""

import os
import subprocess
import sys
import textwrap

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

SCRIPT = textwrap.dedent("""
    import os, sys
    import jax, jax.numpy as jnp
    from repro.launch import cache
    # importing sets nothing: only what JAX read from the environment
    assert (jax.config.jax_compilation_cache_dir
            == os.environ.get("JAX_COMPILATION_CACHE_DIR"))
    if len(sys.argv) > 1:
        cache.DEFAULT_DIR = sys.argv[1]
    print(cache.enable_compile_cache())
    jax.jit(lambda x: jnp.sin(x) * 3 + 1)(jnp.ones(7)).block_until_ready()
""")


def _run(tmp_path, env_dir=None, default_dir=None):
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update({"PYTHONPATH": os.path.join(ROOT, "src"),
                "JAX_PLATFORMS": "cpu",
                "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS": "0",
                "JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES": "0"})
    if env_dir:
        env["JAX_COMPILATION_CACHE_DIR"] = str(env_dir)
    argv = [str(default_dir)] if default_dir else []
    r = subprocess.run([sys.executable, "-c", SCRIPT, *argv], env=env,
                       capture_output=True, text=True, timeout=300,
                       cwd=tmp_path)
    assert r.returncode == 0, r.stderr[-2000:]
    return r.stdout.strip().splitlines()[-1]


def _entries(d):
    return sorted(os.listdir(d)) if os.path.isdir(d) else []


def test_env_dir_takes_the_cache_and_nothing_else_is_set(tmp_path):
    env_dir, default = tmp_path / "env", tmp_path / "default"
    assert _run(tmp_path, env_dir=env_dir, default_dir=default) \
        == str(env_dir)
    assert _entries(env_dir) and not _entries(default)


def test_default_dir_takes_the_cache_without_env(tmp_path):
    default = tmp_path / "default"
    assert _run(tmp_path, default_dir=default) == str(default)
    assert _entries(default)


def test_default_dir_is_the_ignored_repo_cache():
    from repro.launch import cache
    assert str(cache.DEFAULT_DIR) == os.path.join(ROOT, ".jax_cache")
    with open(os.path.join(ROOT, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()
