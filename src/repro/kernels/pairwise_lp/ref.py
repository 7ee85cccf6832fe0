"""Pure-jnp oracle for the pairwise_lp kernel."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.precision import MATMUL_PRECISION


@functools.partial(jax.jit, static_argnames=("clip",))
def pairwise_lp_ref(A, B, na, nb, *, clip: bool = True) -> jax.Array:
    D = (
        na.astype(jnp.float32)[:, None]
        + nb.astype(jnp.float32)[None, :]
        + jnp.matmul(A.astype(jnp.float32), B.astype(jnp.float32).T,
                     precision=MATMUL_PRECISION)
    )
    return jnp.maximum(D, 0.0) if clip else D
