"""The store path's kernels compile for a TPU v5e chip, at the widths the
chip smoke's store phase runs (65,536 keys × 16 chunks × 256 f32 per
replica), with ``interpret=False``.

Nothing runs: the TPU compiler is handed a described v5e and shapes, so
these tests catch what interpret mode cannot (block layouts Mosaic
refuses, VMEM and SMEM overruns, padded relayout copies) at no chip time.
The topology is described inside a fixture — only the worker that runs
this file loads the TPU compiler — and the tests skip where it cannot be
described."""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import delta_join as dj

ROWS, WIDTH, DELTA_ROWS = 65536 * 16, 256, 4096
DOTS = 1 << 20


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _compile(fn, shapes, sharding):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile()


VALS = ((ROWS, WIDTH), jnp.float32)
VERS = ((ROWS,), jnp.int32)
DIGEST = ((ROWS,), jnp.float32)
KERNELS = {
    "delta_join": (dj.delta_join, [VALS, VERS, VALS, VERS]),
    "fused_join_digest": (dj.fused_join_digest, [VALS, VERS, VALS, VERS]),
    "chunk_digest": (dj.chunk_digest, [VALS]),
    "scatter_join": (dj.scatter_join, [
        VALS, VERS, DIGEST, DIGEST, ((DELTA_ROWS,), jnp.int32),
        ((DELTA_ROWS, WIDTH), jnp.float32), ((DELTA_ROWS,), jnp.int32)]),
}


@pytest.mark.parametrize("name", sorted(KERNELS))
def test_store_kernel_compiles_for_v5e(one_chip, name):
    fn, shapes = KERNELS[name]
    compiled = _compile(fn, shapes, one_chip)
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("name", sorted(KERNELS))
def test_store_kernel_views_columns_without_copies(one_chip, name):
    """Version and digest columns reach the kernel as bitcast views of
    their ``[n]`` arrays: a ``[n, 1]`` operand would cost padded
    relayout copies of 128 lanes per row."""
    fn, shapes = KERNELS[name]
    assert _compile(fn, shapes, one_chip).memory_analysis() \
        .temp_size_in_bytes == 0


def test_missing_mask_x64_kernel_compiles_for_v5e(one_chip):
    from repro.core.dotcols import _jax_missing_kernel
    with jax.enable_x64(True):
        compiled = _compile(_jax_missing_kernel(True),
                            [((4,), jnp.int64), ((DOTS // 64,), jnp.int64),
                             ((DOTS,), jnp.int64)], one_chip)
    assert compiled.memory_analysis().argument_size_in_bytes > 8 * DOTS
