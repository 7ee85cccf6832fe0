"""Pallas TPU kernel: packed all-pairs l_p estimate with fused margin epilogue.

Inputs are the packed factors from ``repro.core.pairwise.pack_sketch``
(A (n, K), B (m, K), K = (p-1)k with the sqrt-coefficient folding), plus the
exact marginal norms.  One tiled MXU matmul produces the complete distance
estimate — margins and the >=0 clip are applied in the output tile on the
last reduction step, so the estimate never round-trips to HBM unfused:

    D[i, j] = max(na[i] + nb[j] + sum_K A[i, :] B[j, :], 0)

Grid: (n/bm, m/bn, K/bk); K is the reduction (arbitrary) dimension.
VMEM at defaults (bm=bn=256, bk=512): A 512KB + B 512KB + out 256KB fp32.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.precision import MATMUL_PRECISION

__all__ = ["pairwise_lp_kernel", "pairwise_lp_call"]


def pairwise_lp_kernel(a_ref, b_ref, na_ref, nb_ref, d_ref, *, nsteps: int, clip: bool):
    kstep = pl.program_id(2)

    @pl.when(kstep == 0)
    def _init():
        d_ref[...] = jnp.zeros_like(d_ref)

    a = a_ref[...].astype(jnp.float32)  # (bm, bk)
    b = b_ref[...].astype(jnp.float32)  # (bn, bk)
    d_ref[...] += jax.lax.dot_general(
        a, b, (((1,), (1,)), ((), ())),
        precision=MATMUL_PRECISION,
        preferred_element_type=jnp.float32,
    )

    @pl.when(kstep == nsteps - 1)
    def _epilogue():
        d = d_ref[...] + na_ref[...] + nb_ref[...]  # (bm, 1) + (1, bn)
        if clip:
            d = jnp.maximum(d, 0.0)
        d_ref[...] = d


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "clip", "interpret"))
def pairwise_lp_call(
    A: jax.Array,
    B: jax.Array,
    na: jax.Array,
    nb: jax.Array,
    *,
    bm: int = 256,
    bn: int = 256,
    bk: int = 512,
    clip: bool = True,
    interpret: bool = False,
) -> jax.Array:
    """D (n, m) fp32 = na[:,None] + nb[None,:] + A @ B.T (clipped at 0)."""
    n, K = A.shape
    m, Kb = B.shape
    if K != Kb:
        raise ValueError(f"packed K mismatch {K} vs {Kb}")
    bm, bn, bk = min(bm, n), min(bn, m), min(bk, K)
    npad, mpad, kpad = (-n) % bm, (-m) % bn, (-K) % bk
    if npad or kpad:
        A = jnp.pad(A, ((0, npad), (0, kpad)))
    if mpad or kpad:
        B = jnp.pad(B, ((0, mpad), (0, kpad)))
    if npad:
        na = jnp.pad(na, (0, npad))
    if mpad:
        nb = jnp.pad(nb, (0, mpad))
    npp, Kp = A.shape
    mpp = B.shape[0]
    grid = (npp // bm, mpp // bn, Kp // bk)
    # the norms ride as 2-D (bm, 1) / (1, bn) blocks: Mosaic refuses 1-D
    # blocks whose XLA layout tiling differs from its own
    na = na.astype(jnp.float32).reshape(npp, 1)
    nb = nb.astype(jnp.float32).reshape(1, mpp)
    out = pl.pallas_call(
        functools.partial(pairwise_lp_kernel, nsteps=grid[2], clip=clip),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, s: (i, s)),
            pl.BlockSpec((bn, bk), lambda i, j, s: (j, s)),
            pl.BlockSpec((bm, 1), lambda i, j, s: (i, 0)),
            pl.BlockSpec((1, bn), lambda i, j, s: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, s: (i, j)),
        out_shape=jax.ShapeDtypeStruct((npp, mpp), jnp.float32),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
    )(A, B, na, nb)
    return out[:n, :m]
