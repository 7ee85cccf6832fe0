"""Streaming reductions fused into the strip loop.

The top-k merge keeps a per-row running candidate list of size k and folds
each new strip's local top-k into it, so only (rows, k) state survives a
strip — never the (n, m) matrix.  Tie-breaking matches a dense
``jax.lax.top_k`` over the full row exactly: ``lax.top_k`` resolves equal
values by position, the running list always precedes the new strip in the
concatenation, and running candidates always carry smaller global column
indices than strip candidates (strips are consumed left to right), so equal
distances resolve to the lowest index — same as dense.
"""

from __future__ import annotations

from functools import partial
from typing import Callable, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .backends import strip_distances

__all__ = [
    "streaming_topk",
    "streaming_topk_strips",
    "scan_topk",
    "stacked_topk_scan",
    "stacked_threshold_scan",
    "merge_topk",
    "rerank_topk",
    "strip_bounds",
    "within_tolerance",
]

_IDX_SENTINEL = jnp.iinfo(jnp.int32).max


def strip_bounds(total: int, block: int):
    """(start, stop) strip bounds covering [0, total), never leaving a
    width-1 tail: XLA lowers an (n, K) x (K, 1) strip as a GEMV whose
    K-accumulation order differs from GEMM columns, which would break the
    engine's bit-for-bit match with the dense path.  A single-element
    remainder is absorbed into the preceding strip instead."""
    bounds = []
    c0 = 0
    while c0 < total:
        c1 = min(c0 + block, total)
        if total - c1 == 1:
            c1 = total
        bounds.append((c0, c1))
        c0 = c1
    return bounds


@partial(jax.jit, static_argnames=("c",))
def _strip_topk(D: jax.Array, c: int, col_offset: jax.Array):
    """Per-row best c candidates of one strip, columns globalized."""
    neg, j = jax.lax.top_k(-D, c)
    return -neg, (j + col_offset).astype(jnp.int32)


@partial(jax.jit, static_argnames=("k",))
def merge_topk(vals, idx, cand_vals, cand_idx, k: int):
    """Fold strip candidates into the running (rows, k) lists (ascending)."""
    v = jnp.concatenate([vals, cand_vals], axis=1)
    i = jnp.concatenate([idx, cand_idx], axis=1)
    neg, pos = jax.lax.top_k(-v, k)
    return -neg, jnp.take_along_axis(i, pos, axis=1)


@partial(jax.jit, static_argnames=("k",))
def rerank_topk(vals, idx, k: int):
    """Final (rows, C) -> (rows, k) re-rank with ties broken by LOWEST index.

    ``merge_topk`` resolves ties positionally, which matches dense only while
    the concatenation order tracks global column order (the streaming-strip
    invariant).  A two-stage distributed fan breaks that invariant: candidate
    lists arrive grouped by shard, and round-robin segment placement means
    shard order is not position order.  Sorting each row by (value, index)
    restores the dense contract — equal distances resolve to the smallest
    global position — regardless of the order candidates were gathered in.
    """
    order = jnp.lexsort((idx, vals), axis=-1)
    return (jnp.take_along_axis(vals, order[:, :k], axis=1),
            jnp.take_along_axis(idx, order[:, :k], axis=1))


def streaming_topk_strips(
    strip_fn: Callable[[int, int], jax.Array],
    rows: int,
    cols: int,
    *,
    top_k: int,
    col_block: int,
) -> Tuple[jax.Array, jax.Array]:
    """Generic streaming top-k: ``strip_fn(c0, c1)`` -> (rows, c1-c0) strip.

    Returns (distances (rows, k), column indices (rows, k)), ascending, with
    k = min(top_k, cols).  Works eagerly (strips dispatched one at a time)
    and under tracing (the strip loop unrolls — strip count is static).
    """
    k = min(top_k, cols)
    vals = jnp.full((rows, k), jnp.inf, jnp.float32)
    idx = jnp.full((rows, k), _IDX_SENTINEL, jnp.int32)
    for c0, c1 in strip_bounds(cols, col_block):
        D = strip_fn(c0, c1)
        cand_vals, cand_idx = _strip_topk(D, min(k, c1 - c0), jnp.int32(c0))
        vals, idx = merge_topk(vals, idx, cand_vals, cand_idx, k)
    return vals, idx


def merge_group(n_strips: int, c: int, width: int) -> int:
    """Strips whose candidates ``scan_topk`` merges at once: the largest
    divisor of ``n_strips`` whose ``group * c`` candidates fit in one strip's
    ``width``.  The candidate buffer is then never wider than the distance
    strip the loop holds anyway, and the merge's top-k runs
    ``n_strips / group`` times instead of once per strip."""
    return max(g for g in range(1, n_strips + 1)
               if n_strips % g == 0 and g * c <= width)


def scan_topk(strip: Callable, n_strips: int, init, *, width: int, c: int,
              k: int) -> Tuple[jax.Array, jax.Array]:
    """Fold ``n_strips`` strips into the running (rows, k) lists ``init``
    in one ``lax.scan``: the engine's one compiled strip fold.

    ``strip(i)`` maps a traced strip index to ``(D, live, to_pos)``: the
    (rows, width) distance estimate, the (width,) live mask, and a map from
    the strip's local columns to int32 global positions.  Each strip's dead
    columns are forced to ``+inf`` *after* the estimate (live values stay
    bit-identical), its best ``c`` per row are taken, and every
    ``merge_group(n_strips, c, width)`` strips the candidates are merged
    into the running list.  The running list precedes the candidates and
    the strips keep their order in the concatenation, so equal values
    resolve to the lower position exactly as a merge after every strip
    would: with strips in ascending position order, the dense contract.
    """
    group = merge_group(n_strips, c, width)

    def candidates(i):
        D, live, to_pos = strip(i)
        D = jnp.where(live[None, :], D, jnp.inf)
        neg, j = jax.lax.top_k(-D, c)
        return -neg, to_pos(j)

    def body(carry, g):
        if group == 1:
            cand_vals, cand_idx = candidates(g)
        else:
            _, cand = jax.lax.scan(
                lambda _, t: (None, candidates(g * group + t)), None,
                jnp.arange(group))
            # (group, rows, c) -> (rows, group * c), strips in order
            cand_vals, cand_idx = (
                jnp.moveaxis(x, 0, 1).reshape(x.shape[1], group * c)
                for x in cand)
        return merge_topk(*carry, cand_vals, cand_idx, k), None

    (vals, idx), _ = jax.lax.scan(body, init, jnp.arange(n_strips // group))
    return vals, idx


def stacked_topk_scan(
    strip_fn: Callable,
    strips,
    mask: jax.Array,
    pos: jax.Array,
    *,
    rows: int,
    top_k: int,
) -> Tuple[jax.Array, jax.Array]:
    """Masked streaming top-k over uniform stacked strips (``scan_topk``).

    The strip-unrolled folds (``streaming_topk_strips``) compile one program
    per strip count, so a traced fan over a large corpus pays compile time
    O(corpus).  Here the operands arrive pre-stacked — ``strips`` is a pytree
    of (n_strips, col_block, ...) arrays and ``strip_fn(strip_slice)`` maps
    one (col_block, ...) slice of each leaf to a (rows, col_block) distance
    strip — so a single scanned strip body serves any corpus size.

    ``mask``/``pos`` are (n_strips, col_block): columns with a False mask
    (tombstones and block padding) are forced to ``+inf`` *after* the strip
    estimate, keeping live values bit-identical, and candidate columns are
    reported through ``pos`` (global positions; padding carries the int32
    sentinel).  Strips must be stacked in ascending position order: the merge
    then resolves equal values to the smallest position, the dense contract.

    Returns (vals, positions), both (rows, k) with k = min(top_k, total
    stacked columns), ascending.
    """
    n_strips, col_block = mask.shape
    k = min(top_k, n_strips * col_block)
    init = (
        jnp.full((rows, k), jnp.inf, jnp.float32),
        jnp.full((rows, k), _IDX_SENTINEL, jnp.int32),
    )

    def strip(i):
        p = pos[i]
        D = strip_fn(jax.tree_util.tree_map(lambda x: x[i], strips))
        return D, mask[i], lambda j: p[j].astype(jnp.int32)

    return scan_topk(strip, n_strips, init, width=col_block,
                     c=min(k, col_block), k=k)


def stacked_threshold_scan(
    strip_fn: Callable,
    strips,
    mask: jax.Array,
    *,
    rows: int,
    radius: jax.Array,
    relative: bool = False,
    nq: jax.Array = None,
    nb: jax.Array = None,
) -> jax.Array:
    """Masked threshold criterion over uniform stacked strips via ``lax.scan``.

    The stacked sibling of the strip-unrolled threshold loop: ``strips`` is a
    pytree of (n_strips, col_block, ...) operands, ``strip_fn(strip_slice)``
    maps one (col_block, ...) slice of each leaf to a (rows, col_block)
    distance strip, and the scanned body applies the engine's strict
    ``D < radius`` contract — so one compiled program serves any corpus size,
    and ``radius`` is traced (changing it never recompiles).

    ``mask`` is (n_strips, col_block): columns with a False mask (tombstones
    and block padding) can never hit, applied *after* the strip estimate so
    live values stay bit-identical to the unstacked scan.  With
    ``relative=True`` the criterion is ``D < radius * (nq_i + nb_j)`` over
    the marginal p-norms (``nq`` (rows,), ``nb`` (n_strips, col_block) in
    stack order) — the dedup criterion, same as ``threshold_scan``.

    Returns a (rows, n_strips * col_block) bool hit matrix in stack order;
    only these bools (1 byte/pair, never a distance) leave the device.
    """
    n_strips, col_block = mask.shape
    if relative and (nq is None or nb is None):
        raise ValueError("relative=True needs nq and nb marginal norms")
    xs = (strips, mask, nb) if relative else (strips, mask)

    def body(_, inputs):
        if relative:
            strip_slice, m, nb_s = inputs
            thr = radius * (nq[:, None] + nb_s[None, :])
        else:
            strip_slice, m = inputs
            thr = radius
        D = strip_fn(strip_slice)
        return None, (D < thr) & m[None, :]

    _, hits = jax.lax.scan(body, None, xs)  # (n_strips, rows, col_block)
    return jnp.swapaxes(hits, 0, 1).reshape(rows, n_strips * col_block)


def within_tolerance(got, ref, *, rtol: float, atol: float
                     ) -> Tuple[bool, float]:
    """(ok, max_rel_drift) of a re-tiled fold against its exact reference.

    The conformance check behind the planner's ``ApproxContract``: folds
    whose per-strip solves are not bitwise stable under re-tiling (the
    stacked margin-MLE fan) are admitted only when every value satisfies
    ``|got - ref| <= atol + rtol * |ref|``.  The returned drift is the worst
    observed ``|got - ref| / |ref|`` — the number the contract bounds, and
    what the planner memoizes per operand snapshot.  A shape mismatch fails
    outright (candidate sets diverged: that is a routing bug, not drift).
    """
    got = np.asarray(got, np.float64)
    ref = np.asarray(ref, np.float64)
    if got.shape != ref.shape:
        return False, float("inf")
    if got.size == 0:
        return True, 0.0
    err = np.abs(got - ref)
    ok = bool(np.all(err <= atol + rtol * np.abs(ref)))
    drift = float((err / np.maximum(np.abs(ref), 1e-30)).max())
    return ok, drift


def streaming_topk(
    A: jax.Array,
    na: jax.Array,
    B: jax.Array,
    nb: jax.Array,
    *,
    top_k: int,
    col_block: int,
    backend: str = "xla",
    clip: bool = True,
) -> Tuple[jax.Array, jax.Array]:
    """Streaming top-k over packed factors: smallest estimated distances of
    each row of A against all rows of B, without materializing (n, m)."""

    def strip(c0, c1):
        return strip_distances(
            A, B[c0:c1], na, nb[c0:c1], backend=backend, clip=clip
        )

    return streaming_topk_strips(
        strip, A.shape[0], B.shape[0], top_k=top_k, col_block=col_block
    )
