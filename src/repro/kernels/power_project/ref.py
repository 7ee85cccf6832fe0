"""Pure-jnp oracle for the power_project kernel."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.precision import MATMUL_PRECISION


@functools.partial(jax.jit, static_argnames=("powers",))
def power_project_ref(X: jax.Array, R: jax.Array, powers: tuple[int, ...]) -> jax.Array:
    """U (n, len(powers), k) fp32 = stack_j (X**powers[j]) @ R (naive path)."""
    Xf = X.astype(jnp.float32)
    Rf = R.astype(jnp.float32)
    return jnp.stack([jnp.matmul(Xf**j, Rf, precision=MATMUL_PRECISION)
                      for j in powers], axis=1)
