"""Seeds of any size (``--seed`` may pass 2**31) turned into JAX keys."""

from __future__ import annotations

import numpy as np


def jax_key(seed: int, *stream: int):
    """A threefry key made from ``seed`` and ``stream`` through numpy's
    SeedSequence, which takes integers of any size."""
    import jax
    import jax.numpy as jnp
    words = np.random.SeedSequence([int(seed), *map(int, stream)]) \
        .generate_state(2, dtype=np.uint32)
    return jax.random.wrap_key_data(jnp.asarray(words, jnp.uint32),
                                    impl="threefry2x32")
