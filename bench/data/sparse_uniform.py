"""Rows with a share of nonzero coordinates, each uniform on [0, 1).

Block ``index`` of the corpus is drawn from ``fold_in(key, index)``, so any
block can be made again, on the device, from the seed alone.
"""

from functools import partial

import jax
import jax.numpy as jnp


@partial(jax.jit, static_argnames=("n", "d", "density"))
def batch(key, index, *, n, d, density):
    """Rows [index*n, (index+1)*n) of the corpus, float32 (n, d)."""
    kv, km = jax.random.split(jax.random.fold_in(key, index))
    vals = jax.random.uniform(kv, (n, d), jnp.float32)
    return jnp.where(jax.random.uniform(km, (n, d)) < density, vals, 0.0)
