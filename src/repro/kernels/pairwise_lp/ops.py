"""Jitted public wrapper: sketch-level pairwise distances via the Pallas kernel."""

from __future__ import annotations

import jax

from repro.core.pairwise import pack_sketch
from repro.core.sketch import LpSketch, SketchConfig

from .kernel import pairwise_lp_call
from .ref import pairwise_lp_ref


def pairwise_lp(A, B, na, nb, *, clip=True, use_kernel=True, interpret=False):
    """The kernel (``interpret=True`` runs it through the Pallas interpreter,
    a test-only mode that is never chosen by platform)."""
    if not use_kernel:
        return pairwise_lp_ref(A, B, na, nb, clip=clip)
    return pairwise_lp_call(A, B, na, nb, clip=clip, interpret=interpret)


def pairwise_distances_kernel(
    sa: LpSketch,
    sb: LpSketch | None,
    cfg: SketchConfig,
    *,
    clip: bool = True,
    interpret: bool = False,
) -> jax.Array:
    """Drop-in kernel-backed version of repro.core.pairwise_distances."""
    sb = sa if sb is None else sb
    A, _, na = pack_sketch(sa, cfg)
    _, B, nb = pack_sketch(sb, cfg)
    return pairwise_lp(A, B, na, nb, clip=clip, interpret=interpret)
