"""The traffic generator: one right-hand side per solve, from (seed, index).

Every entry of b is an independent standard normal in float32, drawn on
the device by threefry from a key made of the full ``--seed`` (any
non-negative integer, wider than 32 bits too) and the solve's index in the
run.  Every seed gives the same sizes; only the values differ.
"""
from __future__ import annotations

import numpy as np


def key_data(seed: int) -> np.ndarray:
    """The two uint32 words of the run's key, from the whole seed."""
    return np.random.SeedSequence(int(seed)).generate_state(2, np.uint32)


def normal_fn(n: int):
    """Jitted (key_data, index) -> b of shape (n,), float32."""
    import jax
    import jax.numpy as jnp

    def make(kd, i):
        key = jax.random.wrap_key_data(kd, impl="threefry2x32")
        return jax.random.normal(jax.random.fold_in(key, i), (n,), jnp.float32)

    return make
