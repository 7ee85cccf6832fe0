"""Jitted public wrapper for the fused power+projection kernel.

Runs the Pallas kernel, or the Pallas interpreter when a test asks for it
(``interpret=True``; never chosen by platform), and integrates with the
sketching API: ``sketch_via_kernel`` produces the same ``LpSketch`` as
``repro.core.sketch`` (same streamed R tiles)."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.decomposition import interaction_orders
from repro.core.projections import projection_matrix
from repro.core.sketch import LpSketch, SketchConfig, _matrix_key, sketch_moments

from .kernel import power_project_call
from .ref import power_project_ref


def power_project(X, R, powers, *, use_kernel: bool = True, interpret: bool = False):
    """Dispatch between the Pallas kernel and the jnp reference."""
    if not use_kernel:
        return power_project_ref(X, R, tuple(powers))
    return power_project_call(X, R, tuple(powers), interpret=interpret)


def sketch_via_kernel(
    X: jax.Array, key: jax.Array, cfg: SketchConfig, *, interpret: bool = False
) -> LpSketch:
    """LpSketch built by the fused kernel — same R stream as repro.core.sketch."""
    n, D = X.shape
    if cfg.fractional:
        # α-stable sketch: power 1 only — the fused kernel consumes the
        # streamed stable R tiles exactly like the even-p families
        R = projection_matrix(_matrix_key(key, 0), D, cfg.k, cfg.projection)
        U = power_project(X, R, (1,), interpret=interpret)
    elif cfg.strategy == "basic":
        R = projection_matrix(_matrix_key(key, 0), D, cfg.k, cfg.projection)
        powers = tuple(range(1, cfg.p))
        U = power_project(X, R, powers, interpret=interpret)
    else:
        ua, ub = [], []
        for a, c, _ in interaction_orders(cfg.p):
            m = c
            R = projection_matrix(_matrix_key(key, m), D, cfg.k, cfg.projection)
            both = power_project(X, R, (a, c), interpret=interpret)
            ua.append(both[:, 0])
            ub.append(both[:, 1])
        U = jnp.stack(ua + ub, axis=1)
    return LpSketch(U=U.astype(cfg.projection.dtype), moments=sketch_moments(X, cfg))
