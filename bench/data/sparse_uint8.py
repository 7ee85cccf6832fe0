"""Rows of byte values: a share of the coordinates nonzero, each an integer
drawn uniformly from 1 to 255, held as float32 (the BIGANN / SIFT1B
descriptors are 128 unsigned bytes a row).

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
    vals = jax.random.randint(kv, (n, d), 1, 256).astype(jnp.float32)
    return jnp.where(jax.random.uniform(km, (n, d)) < density, vals, 0.0)
