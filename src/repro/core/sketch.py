"""Power sketches for l_p distance estimation (paper §2.1, §2.2, §3 + the
fractional-p α-stable lineage).

Even p (the paper): given a row x in R^D, the sketch holds k-dimensional
projections of the power vectors x^1 ... x^{p-1} plus the exact even power
moments (one linear scan).  Two strategies, exactly as in the paper:

- ``basic``:       one R for every order;  U[j-1] = (x^j)^T R           (p-1 vectors)
- ``alternative``: term m = 1..p-1 gets its own independent R^(m);
                   Ua[m-1] = (x^{p-m})^T R^(m)   (row acting as "x"),
                   Ub[m-1] = (x^m)^T R^(m)       (row acting as "y").

Fractional 0 < p <= 2 (``projection.family`` in ``stable``/``stable_sparse``):
the sketch is one α-stable projection of x itself, U[:, 0] = x^T R with
alpha = p, and the single "moment" column is sum_i |x_i|^p (the marginal
norm).  The geometric-mean estimator (core/stable.py) consumes sketch
*differences*, whose coordinates are S(p, ||x - y||_p) draws.  The sparse
family's blocks are ingested with a gather (O(density) of the dense FLOPs)
over the exact (indices, values) pairs the dense tile scatter-adds.

Estimates between two rows only need sketches built with the *same*
(key, config); the streamed, counter-based R tiles guarantee that across
shards, hosts, and restarts.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from repro.precision import MATMUL_PRECISION

from .decomposition import interaction_orders, power_moments
from .projections import (
    ProjectionSpec,
    projection_block,
    projection_sparse_block,
)
from .registry import FRACTIONAL_P, SKETCH_EVEN_P

__all__ = ["SketchConfig", "LpSketch", "sketch", "sketch_block_contrib",
           "sketch_moments"]

_BASIC_MATRIX_ID = 0


@dataclasses.dataclass(frozen=True)
class SketchConfig:
    """Static configuration of an l_p sketch.

    Attributes:
      p: distance order.  Even >= 4 for the paper's power sketches; any
        fractional 0 < p <= 2 when the projection family is α-stable.
      k: sketch width (number of projection samples).
      strategy: ``basic`` (one R) or ``alternative`` (p-1 independent R's;
        even-p only).
      projection: the R family (normal / uniform / threepoint SubG(s) /
        stable / stable_sparse).  Stable families pin ``alpha`` to p.
      block_d: streaming block over the D axis; R tiles are (block_d, k).
    """

    p: float = 4
    k: int = 64
    strategy: str = "basic"
    projection: ProjectionSpec = dataclasses.field(default_factory=ProjectionSpec)
    block_d: int = 2048

    def __post_init__(self):
        if self.projection.is_stable:
            FRACTIONAL_P.check(self.p, what="an α-stable sketch")
            if self.strategy != "basic":
                raise ValueError(
                    "stable projections support only the basic strategy")
            if float(self.projection.alpha) != float(self.p):
                # the stability index IS the distance order; pin it so a
                # mismatched spec can't silently estimate the wrong norm
                object.__setattr__(
                    self, "projection",
                    dataclasses.replace(self.projection, alpha=float(self.p)))
        else:
            if not SKETCH_EVEN_P.contains(self.p):
                raise ValueError(f"p must be even and >= 4, got {self.p}")
            object.__setattr__(self, "p", int(self.p))
        if self.strategy not in ("basic", "alternative"):
            raise ValueError(f"unknown strategy {self.strategy!r}")

    @property
    def fractional(self) -> bool:
        """True for the α-stable fractional-p sketch layout."""
        return self.projection.is_stable

    @property
    def num_orders(self) -> int:
        return 1 if self.fractional else self.p - 1

    @property
    def num_moments(self) -> int:
        """Moment columns per row: the p-1 even power moments, or the single
        |x|^p marginal for fractional p."""
        return 1 if self.fractional else self.p - 1

    @property
    def vectors_per_row(self) -> int:
        if self.fractional:
            return 1
        return self.p - 1 if self.strategy == "basic" else 2 * (self.p - 1)


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class LpSketch:
    """Sketch of n rows.

    U:  basic: (n, p-1, k), U[:, j-1] = (x^j)^T R.
        alternative: (n, 2(p-1), k) = [Ua | Ub] stacked on axis 1;
        Ua[:, m-1] = (x^{p-m})^T R^(m), Ub[:, m-1] = (x^m)^T R^(m).
        fractional: (n, 1, k), U[:, 0] = x^T R (α-stable R).
    moments: (n, num_moments) — even moments col j-1 = sum_i x_i^{2j}, or
        the single sum_i |x_i|^p column for fractional p.
    """

    U: jax.Array
    moments: jax.Array

    @property
    def n(self) -> int:
        return self.U.shape[0]

    def norm_pp(self, p) -> jax.Array:
        """||x||_p^p per row."""
        if self.moments.shape[-1] == 1:  # fractional layout: the one column
            return self.moments[..., 0]
        return self.moments[..., int(p) // 2 - 1]

    def row(self, i) -> "LpSketch":
        return LpSketch(self.U[i][None], self.moments[i][None])


def _matrix_key(key: jax.Array, matrix_id: int) -> jax.Array:
    return jax.random.fold_in(key, matrix_id)


def _powers(xb: jax.Array, p: int) -> jax.Array:
    """(n, p-1, bd) stack of x^1..x^{p-1} for a (n, bd) block."""
    pw = [xb]
    for _ in range(p - 2):
        pw.append(pw[-1] * xb)
    return jnp.stack(pw, axis=1)


def sketch_moments(X: jax.Array, cfg: SketchConfig) -> jax.Array:
    """(n, num_moments) exact moment columns for rows (or a D-block of rows
    — moments are sums over D, so block contributions add)."""
    if cfg.fractional:
        X = X.astype(jnp.promote_types(X.dtype, jnp.float32))
        return jnp.sum(jnp.abs(X) ** float(cfg.p), axis=-1, keepdims=True)
    return power_moments(X, cfg.p)


def sketch_block_contrib(
    xb: jax.Array, block_index: jax.Array, key: jax.Array, cfg: SketchConfig
) -> jax.Array:
    """Contribution of one D-block (n, block_d) to the projection part of the
    sketch: (n, num_vectors, k).  Summing over all blocks gives ``LpSketch.U``.

    This is also the reference semantics the Pallas ``power_project`` kernel
    implements (see kernels/power_project/ref.py).  The ``stable_sparse``
    family never materializes its R tile here: the block contribution is a
    gather over the tile's (indices, values) pairs — m = density * block_d
    multiply-adds per output instead of block_d.
    """
    p, k = cfg.p, cfg.k
    if cfg.fractional:
        xf = xb.astype(cfg.projection.dtype)
        mkey = _matrix_key(key, _BASIC_MATRIX_ID)
        if cfg.projection.family == "stable_sparse":
            idx, vals = projection_sparse_block(
                mkey, block_index, xb.shape[-1], k, cfg.projection)
            # (n, m, k) gather then contract m: the sparse ingest fast path
            u = jnp.einsum("nmk,mk->nk", xf[:, idx], vals,
                           precision=MATMUL_PRECISION)
        else:
            R = projection_block(mkey, block_index, xb.shape[-1], k,
                                 cfg.projection)
            u = jnp.matmul(xf, R, precision=MATMUL_PRECISION)
        return u[:, None, :]
    pw = _powers(xb.astype(cfg.projection.dtype), p)  # (n, p-1, bd)
    if cfg.strategy == "basic":
        R = projection_block(_matrix_key(key, _BASIC_MATRIX_ID), block_index,
                             xb.shape[-1], k, cfg.projection)
        return jnp.einsum("njd,dk->njk", pw, R, precision=MATMUL_PRECISION)
    # alternative: term m uses R^(m) for both roles
    ua, ub = [], []
    for a, c, _ in interaction_orders(p):  # a = p-m, c = m
        m = c
        R = projection_block(_matrix_key(key, m), block_index,
                             xb.shape[-1], k, cfg.projection)
        ua.append(jnp.matmul(pw[:, a - 1], R, precision=MATMUL_PRECISION))
        ub.append(jnp.matmul(pw[:, c - 1], R, precision=MATMUL_PRECISION))
    return jnp.stack(ua + ub, axis=1)


@partial(jax.jit, static_argnames=("cfg", "block_offset_static"))
def _sketch_dense(
    X: jax.Array, key: jax.Array, cfg: SketchConfig, block_offset_static: int = 0
) -> LpSketch:
    n, D = X.shape
    bd = min(cfg.block_d, D)
    pad = (-D) % bd
    if pad:
        X = jnp.pad(X, ((0, 0), (0, pad)))  # zeros are inert for powers/moments
    nblocks = X.shape[1] // bd
    Xb = X.reshape(n, nblocks, bd)

    def body(acc, i):
        contrib = sketch_block_contrib(Xb[:, i], block_offset_static + i, key, cfg)
        return acc + contrib, None

    nvec = cfg.vectors_per_row
    U0 = jnp.zeros((n, nvec, cfg.k), cfg.projection.dtype)
    U, _ = jax.lax.scan(body, U0, jnp.arange(nblocks))
    return LpSketch(U=U, moments=sketch_moments(X, cfg))


def sketch(
    X: jax.Array,
    key: jax.Array,
    cfg: Optional[SketchConfig] = None,
    *,
    block_offset: int = 0,
) -> LpSketch:
    """Sketch the rows of X (n, D).

    ``block_offset`` shifts the R block counter — used by distributed shards
    that own columns [offset*block_d, ...) of the global matrix so every shard
    draws its own slice of the *same* global R.
    """
    cfg = cfg or SketchConfig()
    if X.ndim != 2:
        raise ValueError(f"X must be (n, D), got {X.shape}")
    return _sketch_dense(X, key, cfg, block_offset_static=block_offset)
