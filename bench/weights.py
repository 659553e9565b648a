"""The keyed draw that every model module makes its weights with
(``bench/models/<model_type>.py``).

Every weight is uniform, drawn from a key that depends only on (seed,
stream, layer): a model module numbers its leaves' streams, makes its
served tree on the device in one jitted call, and makes each layer's
float32 leaves for the plain reference from the same keys.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

NORM_SPREAD = 0.1        # norm weights are 1 + U(-0.1, 0.1)


def seed_key(seed: int):
    """A PRNG key for any whole-number seed (64 bits and more fold in)."""
    key = jax.random.PRNGKey(0)
    seed = int(seed)
    while True:
        key = jax.random.fold_in(key, np.uint32(seed & 0xFFFFFFFF))
        seed >>= 32
        if seed == 0:
            return key


def draw(key, stream: int, layer, shape, half):
    """Float32 U(-half, half) of ``shape`` from the key of (stream, layer)."""
    k = jax.random.fold_in(jax.random.fold_in(key, stream), layer)
    return jax.random.uniform(k, shape, jnp.float32, -half, half)
