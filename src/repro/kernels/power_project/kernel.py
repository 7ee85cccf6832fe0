"""Pallas TPU kernel: fused power + projection (the paper's linear scan).

Computes U[:, j, :] = (X ** powers[j]) @ R for a static tuple of powers,
reading each X tile from HBM exactly ONCE: the powers are formed in VMEM
(VPU elementwise) and immediately contracted on the MXU against the resident
R tile.  The naive path reads X len(powers) times and materializes every
power vector in HBM — this kernel raises arithmetic intensity from O(k) to
O(len(powers) * k) per element loaded.

Grid: (n / bm, D / bd) with the D axis as the reduction (arbitrary) dimension;
the output block (bm, len(powers), k) is revisited across the D steps and
accumulated in fp32.

BlockSpec tiling (VMEM budget, defaults bm=256, bd=512, k<=512, p-1=3 powers):
  X tile   (bm, bd)            256*512*4   = 512 KiB
  R tile   (bd, k)             512*512*4   = 1   MiB
  U tile   (bm, p-1, k) fp32   256*3*512*4 = 1.5 MiB     -> ~3 MiB << 16 MiB VMEM
MXU alignment: bm, bd, k should be multiples of (8, 128) lanes; the wrapper
pads as needed.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.precision import MATMUL_PRECISION

__all__ = ["power_project_kernel", "power_project_call"]


def power_project_kernel(x_ref, r_ref, u_ref, *, powers: tuple[int, ...]):
    d_step = pl.program_id(1)

    @pl.when(d_step == 0)
    def _init():
        u_ref[...] = jnp.zeros_like(u_ref)

    x = x_ref[...].astype(jnp.float32)  # (bm, bd)
    r = r_ref[...].astype(jnp.float32)  # (bd, k)
    # incremental powers: x^1, x^2, ... computed once each on the VPU
    max_pow = max(powers)
    xp = x
    partials = {}
    for j in range(1, max_pow + 1):
        if j in powers:
            partials[j] = jnp.dot(xp, r, precision=MATMUL_PRECISION,
                                  preferred_element_type=jnp.float32)
        if j < max_pow:
            xp = xp * x
    for slot, j in enumerate(powers):
        u_ref[:, slot, :] += partials[j]


@functools.partial(
    jax.jit, static_argnames=("powers", "bm", "bd", "interpret")
)
def power_project_call(
    X: jax.Array,
    R: jax.Array,
    powers: tuple[int, ...],
    *,
    bm: int = 256,
    bd: int = 512,
    interpret: bool = False,
) -> jax.Array:
    """U (n, len(powers), k) fp32 = stack_j (X**powers[j]) @ R.

    Pads n to bm and D to bd (zeros are inert: 0**j = 0 contributes nothing).
    """
    n, D = X.shape
    Dr, k = R.shape
    if D != Dr:
        raise ValueError(f"X D={D} vs R D={Dr}")
    bm = min(bm, max(8, n))
    bd = min(bd, D)
    npad = (-n) % bm
    dpad = (-D) % bd
    if npad or dpad:
        X = jnp.pad(X, ((0, npad), (0, dpad)))
    if dpad:
        R = jnp.pad(R, ((0, dpad), (0, 0)))
    npads, Dp = X.shape
    grid = (npads // bm, Dp // bd)
    out = pl.pallas_call(
        functools.partial(power_project_kernel, powers=powers),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bd), lambda i, d: (i, d)),
            pl.BlockSpec((bd, k), lambda i, d: (d, 0)),
        ],
        out_specs=pl.BlockSpec((bm, len(powers), k), lambda i, d: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((npads, len(powers), k), jnp.float32),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
    )(X, R)
    return out[:n]
