"""All-pairs / KNN distance estimation from sketches — the O(n^2 k) path.

The paper evaluates pair estimates term by term (p-1 rank-k dot products).
We pack the order-matched sketch vectors with sign-folded sqrt coefficients:

    A[i] = concat_m sqrt(|c_m|/k) * u^{(i)}_{p-m}
    B[i] = concat_m sign(c_m) sqrt(|c_m|/k) * u^{(i)}_{m}

so the *entire* interaction estimate for every pair is ONE (n, (p-1)k) x
((p-1)k, n) matmul, with the marginal norms applied as a rank-1 epilogue:

    D_hat = ||x_i||_p^p + ||x_j||_p^p + (A @ B^T)[i, j]

This packing is exact (not an approximation) and is the beyond-paper fusion
the Pallas ``pairwise_lp`` kernel implements on the MXU.  Symmetry
d(i,j) = d(j,i) holds because c_m = c_{p-m} for even p.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from repro.precision import MATMUL_PRECISION

from .decomposition import interaction_orders
from .estimators import margin_mle_root
from .sketch import LpSketch, SketchConfig

__all__ = ["pack_sketch", "pack_right", "pairwise_distances", "pairwise_margin_mle",
           "knn"]


@partial(jax.jit, static_argnames=("cfg",))
def pack_sketch(sk: LpSketch, cfg: SketchConfig):
    """(A, B, norms): packed left/right factors + marginal p-norms."""
    p, k = cfg.p, cfg.k
    no = cfg.num_orders
    A_parts, B_parts = [], []
    for a, c, coef in interaction_orders(p):
        m = c
        root = math.sqrt(abs(coef) / k)
        sgn = math.copysign(1.0, coef)
        if cfg.strategy == "basic":
            ua, vb = sk.U[:, a - 1], sk.U[:, c - 1]
        else:
            ua, vb = sk.U[:, m - 1], sk.U[:, no + m - 1]
        A_parts.append(root * ua)
        B_parts.append(sgn * root * vb)
    A = jnp.concatenate(A_parts, axis=-1)
    B = jnp.concatenate(B_parts, axis=-1)
    return A, B, sk.norm_pp(p)


@partial(jax.jit, static_argnames=("cfg",))
def pack_right(sk: LpSketch, cfg: SketchConfig):
    """(B, norms): ``pack_sketch``'s right factor and marginal norms alone.

    What a stored corpus segment keeps.  The unused left factor is never
    built, so a burst of packs dispatched together (a fan's first query
    packs every sealed segment) holds no dead left factors in device memory
    until their programs finish.  Same ops as ``pack_sketch``, same bits."""
    _, B, norms = pack_sketch(sk, cfg)
    return B, norms


@partial(jax.jit, static_argnames=("cfg", "clip", "zero_diag"))
def pairwise_distances(
    sa: LpSketch,
    sb: Optional[LpSketch],
    cfg: SketchConfig,
    *,
    clip: bool = True,
    zero_diag: bool = False,
) -> jax.Array:
    """(n, m) estimated l_p^p distances between rows of two sketch sets.

    ``sb=None`` means self-pairs (symmetric; ``zero_diag`` zeroes the
    diagonal, whose true distance is 0).
    """
    self_pairs = sb is None
    sb = sa if self_pairs else sb
    A, _, na = pack_sketch(sa, cfg)
    _, B, nb = pack_sketch(sb, cfg)
    D = na[:, None] + nb[None, :] + jnp.matmul(A, B.T, precision=MATMUL_PRECISION)
    if clip:
        D = jnp.maximum(D, 0.0)
    if zero_diag and self_pairs:
        D = D * (1.0 - jnp.eye(D.shape[0], dtype=D.dtype))
    return D


@partial(jax.jit, static_argnames=("cfg", "newton_steps", "clip"))
def pairwise_margin_mle(
    sa: LpSketch,
    sb: Optional[LpSketch],
    cfg: SketchConfig,
    *,
    newton_steps: int = 2,
    clip: bool = True,
) -> jax.Array:
    """All-pairs margin-MLE distances (Lemma 4 applied per term, vectorized).

    Costs p-1 rank-k matmuls for the t_m matrices plus O(n m (p-1)) Newton
    work; per-row ||u||^2 margins broadcast, so still O(n^2 k) overall.
    """
    sb_ = sa if sb is None else sb
    p, k = cfg.p, cfg.k
    no = cfg.num_orders
    D = sa.norm_pp(p)[:, None] + sb_.norm_pp(p)[None, :]
    for a, c, coef in interaction_orders(p):
        m = c
        if cfg.strategy == "basic":
            U, V = sa.U[:, a - 1], sb_.U[:, c - 1]
        else:
            U, V = sa.U[:, m - 1], sb_.U[:, no + m - 1]
        t = jnp.matmul(U, V.T, precision=MATMUL_PRECISION)
        nu = jnp.sum(U * U, axis=-1)[:, None]
        nv = jnp.sum(V * V, axis=-1)[None, :]
        Mx = sa.moments[:, a - 1][:, None]
        My = sb_.moments[:, c - 1][None, :]
        a_hat = margin_mle_root(t, nu, nv, Mx, My, k, newton_steps)
        D = D + coef * a_hat
    return jnp.maximum(D, 0.0) if clip else D


def knn(
    queries: LpSketch,
    corpus: LpSketch,
    cfg: SketchConfig,
    top_k: int = 10,
    *,
    mle: bool = False,
    engine_cfg=None,
):
    """Top-k nearest corpus rows per query under estimated l_p^p distance.

    Returns (distances (q, k), indices (q, k)), ascending, k = min(top_k, m).
    Streams (row_block, col_block) strips through ``repro.engine`` with a
    fused per-row candidate merge — the (q, m) matrix never materializes, so
    the corpus can exceed device memory.  With ``mle=False`` results are
    identical to the dense ``top_k(pairwise_distances(...))`` path on CPU
    (same values, same tie-breaking); ``mle=True`` strips at non-default
    block sizes can differ from the dense path by fp noise (different XLA
    small-matmul lowerings).
    """
    from repro.engine import pairwise as engine_pairwise  # lazy: avoids cycle

    from . import registry

    return engine_pairwise(
        queries, corpus, cfg,
        reduce="topk", top_k=top_k,
        estimator=registry.MARGIN_MLE if mle else registry.DEFAULT_ESTIMATOR,
        engine=engine_cfg,
    )
