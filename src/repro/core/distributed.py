"""Distributed sketching & pairwise estimation (shard_map, mesh-native).

Layout (paper's data matrix A (n, D) at cluster scale):

  * A is sharded rows -> ``data`` axis, columns -> ``model`` axis.
  * Each shard sketches its column slice against *its slice of the global R*
    (counter-based tiles, offset by the shard's global column-block index) and
    the k-dim partials are psum'd over ``model`` — the projection contracts
    over D, so the only collective is an all-reduce of (n_loc, nvec, k),
    k << D.  Marginal moments reduce the same way.
  * All-pairs blocks keep rows local and all-gather the (much smaller) packed
    factors of the opposing side over ``data``.

The multi-pod mesh prepends a ``pod`` axis: rows are sharded over
(pod, data) jointly — pass ``data_axes=("pod", "data")``.
"""

from __future__ import annotations

from functools import partial
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.lax import pcast
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from .pairwise import pack_sketch
from .sketch import LpSketch, SketchConfig, sketch, sketch_moments

__all__ = [
    "sketch_sharded",
    "pairwise_sharded",
    "knn_sharded",
    "stacked_topk_shards",
    "stacked_mle_topk_shards",
    "stacked_threshold_shards",
    "mesh_shard_devices",
    "mesh_replica_devices",
]


def _tuple(axes) -> tuple:
    return tuple(axes) if isinstance(axes, (tuple, list)) else (axes,)


def mesh_shard_devices(mesh: Mesh, data_axes: Sequence[str] | str = "data"):
    """Ordered per-shard device list for a mesh's data axes.

    Flattens ``data_axes`` in row-major order (the same order
    ``jax.lax.axis_index`` composes in ``knn_sharded``) and takes the first
    device along every other axis — shard i of a segment placement and shard
    i of a ``shard_map`` fan land on the same physical device.
    """
    data_axes = _tuple(data_axes)
    names = list(mesh.axis_names)
    perm = [names.index(a) for a in data_axes] + [
        i for i, n in enumerate(names) if n not in data_axes
    ]
    arr = np.transpose(mesh.devices, perm)
    n_shards = int(np.prod([mesh.shape[a] for a in data_axes]))
    return list(arr.reshape(n_shards, -1)[:, 0])


def mesh_replica_devices(mesh: Mesh, *, replica_axis: str = "replica",
                         data_axes: Sequence[str] | str = "data"):
    """Per-replica ordered shard-device lists for a serving mesh.

    Returns ``[devices_of_replica_0, devices_of_replica_1, ...]`` where each
    entry is the ``mesh_shard_devices``-ordered device list of one row of
    the ``replica`` axis — replica r's shard i lands on ``out[r][i]``.
    Queries go to exactly one replica, so each row is an independent serving
    plane (``repro.serve.ReplicaSet`` builds one lane per row); there is no
    cross-replica collective anywhere in the serving stack.  A mesh without
    a replica axis is one replica."""
    names = list(mesh.axis_names)
    if replica_axis not in names:
        return [mesh_shard_devices(mesh, data_axes)]
    data_axes = _tuple(data_axes)
    perm = ([names.index(replica_axis)]
            + [names.index(a) for a in data_axes]
            + [i for i, n in enumerate(names)
               if n != replica_axis and n not in data_axes])
    arr = np.transpose(mesh.devices, perm)
    n_rep = mesh.shape[replica_axis]
    n_shards = int(np.prod([mesh.shape[a] for a in data_axes]))
    arr = arr.reshape(n_rep, n_shards, -1)
    return [list(arr[r, :, 0]) for r in range(n_rep)]


def sketch_sharded(
    X: jax.Array,
    key: jax.Array,
    cfg: SketchConfig,
    mesh: Mesh,
    *,
    data_axes: Sequence[str] | str = "data",
    model_axis: str = "model",
) -> LpSketch:
    """Sketch a (n, D) matrix sharded (rows=data_axes, cols=model_axis).

    Requires D % (model_axis_size * cfg.block_d) == 0 so every shard draws
    whole R tiles.  Returns an LpSketch sharded over rows and replicated over
    ``model_axis`` (ready for pairwise work).
    """
    data_axes = _tuple(data_axes)
    msize = mesh.shape[model_axis]
    n, D = X.shape
    if D % (msize * cfg.block_d) != 0:
        raise ValueError(
            f"D={D} must be divisible by model_axis_size*block_d="
            f"{msize}*{cfg.block_d}"
        )
    blocks_per_shard = D // msize // cfg.block_d

    def local_sketch(xl: jax.Array) -> LpSketch:
        midx = jax.lax.axis_index(model_axis)
        # block_offset is dynamic per shard; fold it into the key stream by
        # scanning local blocks with a dynamic global index.  Moments are
        # accumulated in the SAME block scan — one linear pass over the data
        # (the paper's assumption, and what the fused Pallas kernel does);
        # computing power_moments on the full row materializes p-1 full-width
        # power intermediates (dry-run: 43 GB/device at D=134M).
        nloc = xl.shape[0]
        xb = xl.reshape(nloc, blocks_per_shard, cfg.block_d)

        from .sketch import sketch_block_contrib  # local import to avoid cycle

        def body(carry, i):
            U, M = carry
            gidx = midx * blocks_per_shard + i
            U = U + sketch_block_contrib(xb[:, i], gidx, key, cfg)
            M = M + sketch_moments(xb[:, i], cfg)
            return (U, M), None

        U0 = jnp.zeros((nloc, cfg.vectors_per_row, cfg.k), cfg.projection.dtype)
        M0 = jnp.zeros((nloc, cfg.num_moments), jnp.float32)
        U0 = pcast(U0, (*data_axes, model_axis), to="varying")
        M0 = pcast(M0, (*data_axes, model_axis), to="varying")
        (U, M), _ = jax.lax.scan(body, (U0, M0), jnp.arange(blocks_per_shard))
        U = jax.lax.psum(U, model_axis)
        moments = jax.lax.psum(M, model_axis)
        return LpSketch(U=U, moments=moments)

    in_spec = P(data_axes, model_axis)
    out_spec = LpSketch(U=P(data_axes, None, None), moments=P(data_axes, None))
    return shard_map(
        local_sketch, mesh=mesh, in_specs=(in_spec,), out_specs=out_spec
    )(X)


def pairwise_sharded(
    sk: LpSketch,
    cfg: SketchConfig,
    mesh: Mesh,
    *,
    data_axes: Sequence[str] | str = "data",
    clip: bool = True,
    reduce: str = "full",
    radius: Optional[float] = None,
    relative: bool = False,
    engine_cfg=None,
):
    """Self all-pairs distances for a row-sharded sketch.

    ``reduce="full"`` (default): (n, n) distances sharded rows over
    ``data_axes`` — each shard computes its (n_loc, n) strip against the
    all-gathered packed right factor.

    ``reduce="threshold"``: the engine's threshold reduction routed through
    the per-shard strips — each shard streams its (n_loc, n) block
    ``col_block`` columns at a time and only a *bool* hit mask (4 bytes/pair
    smaller than fp32 distances, and never the distances themselves) leaves
    the shard; the host converts to (rows, cols) index pairs in row-major
    order, the same contract (and bit-identical pairs on CPU) as
    ``engine.pairwise(..., reduce="threshold")``.  ``relative=True`` tests
    D < radius * (||x_i||_p^p + ||x_j||_p^p), the dedup criterion.
    """
    from repro.engine import EngineConfig, default_backend, strip_distances
    from repro.engine.reduce import strip_bounds

    if reduce not in ("full", "threshold"):
        raise ValueError(f"reduce must be 'full' or 'threshold', got {reduce!r}")
    if reduce == "threshold" and radius is None:
        raise ValueError("reduce='threshold' requires a radius")

    data_axes = _tuple(data_axes)
    A, B, norms = pack_sketch(sk, cfg)
    backend = default_backend()
    spec_rows = P(data_axes, None)
    spec_vec = P(data_axes)

    def _gather(b_loc, n_loc):
        b_all, n_all = b_loc, n_loc
        for ax in data_axes:
            b_all = jax.lax.all_gather(b_all, ax, tiled=True)
            n_all = jax.lax.all_gather(n_all, ax, tiled=True)
        return b_all, n_all

    if reduce == "full":

        def strip(a_loc, b_loc, n_loc, n_all_in):
            b_all, n_all = _gather(b_loc, n_all_in)
            return strip_distances(a_loc, b_all, n_loc, n_all,
                                   backend=backend, clip=clip)

        return shard_map(
            strip,
            mesh=mesh,
            in_specs=(spec_rows, spec_rows, spec_vec, spec_vec),
            out_specs=spec_rows,
        )(A, B, norms, norms)

    # reduce == "threshold"
    n = sk.n
    backend, _, col_block = (engine_cfg or EngineConfig()).resolve()
    bounds = strip_bounds(n, col_block)

    def local_mask(a_loc, b_loc, n_loc, n_all_in):
        b_all, n_all = _gather(b_loc, n_all_in)
        hits = []
        # the radius comparison is a float32 contract shared with the index
        # scans: cast once, before any scaling, so a float64 python/numpy
        # radius can never flip a pair sitting exactly at the boundary
        r32 = jnp.float32(radius)
        for c0, c1 in bounds:  # static unroll: one col strip live at a time
            D = strip_distances(a_loc, b_all[c0:c1], n_loc, n_all[c0:c1],
                                backend=backend, clip=clip)
            if relative:
                scale = n_loc[:, None] + n_all[None, c0:c1]
                hits.append(D < r32 * scale)
            else:
                hits.append(D < r32)
        return jnp.concatenate(hits, axis=1)

    mask = shard_map(
        local_mask,
        mesh=mesh,
        in_specs=(spec_rows, spec_rows, spec_vec, spec_vec),
        out_specs=spec_rows,
    )(A, B, norms, norms)
    rows, cols = np.nonzero(np.asarray(mask))  # row-major, == engine order
    return rows, cols


@partial(
    jax.jit,
    static_argnames=("mesh", "top_k", "col_block", "backend", "data_axes"),
)
def stacked_topk_shards(
    Aq: jax.Array,
    nq: jax.Array,
    B_stack: jax.Array,
    nb_stack: jax.Array,
    mask_stack: jax.Array,
    pos_stack: jax.Array,
    *,
    mesh: Mesh,
    top_k: int,
    col_block: int,
    backend: str = "xla",
    data_axes: Sequence[str] | str = "data",
):
    """Stage 1 of a sharded top-k fan as ONE ``shard_map`` over stacked blocks.

    Every shard holds an equal-shape block of packed corpus factors —
    ``B_stack`` (S, R, W) / ``nb_stack`` (S, R) placed along ``data_axes`` —
    padded with masked-off rows so all shards run the identical SPMD program.
    The (tiny, replicated) query factors stream each shard's R rows through
    the engine's scanned strip merge concurrently on all shards; only the
    per-shard (q, k) candidate lists ever leave a device, never a distance
    strip, and no collective runs at all — stage 2 (the host-side
    ``rerank_topk`` lexsort over the gathered lists) owns the merge.

    ``mask_stack`` masks tombstones and padding to ``+inf`` after the strip
    estimate and ``pos_stack`` globalizes candidates, so live values — and,
    after the (value, position) re-rank, tie-broken ids — are bit-identical
    to the single-host fan.  R must be a multiple of ``col_block``.

    Returns (vals, positions), both (S, q, k) with k = min(top_k, R),
    sharded over ``data_axes`` on the leading axis.
    """
    from repro.engine.backends import strip_distances
    from repro.engine.reduce import stacked_topk_scan

    data_axes = _tuple(data_axes)
    q = Aq.shape[0]
    _, R, W = B_stack.shape
    if R % col_block != 0:
        raise ValueError(f"stack rows {R} not a multiple of col_block {col_block}")
    n_strips = R // col_block
    k = min(top_k, R)

    def local_topk(aq, nq_, b, nb_, m, p):
        # squeeze the shard axis: each shard sees one (R, ...) block
        b, nb_, m, p = b[0], nb_[0], m[0], p[0]

        def strip_fn(xs):
            bb, nbb = xs
            return strip_distances(aq, bb, nq_, nbb, backend=backend, clip=True)

        # trace-time annotation only: names this region in jax.profiler /
        # TensorBoard captures, zero runtime cost
        with jax.named_scope("stage1.stacked_topk"):
            vals, pos = stacked_topk_scan(
                strip_fn,
                (b.reshape(n_strips, col_block, W),
                 nb_.reshape(n_strips, col_block)),
                m.reshape(n_strips, col_block),
                p.reshape(n_strips, col_block),
                rows=q, top_k=k,
            )
        return vals[None], pos[None]

    spec_blk = P(data_axes, None, None)
    spec_row = P(data_axes, None)
    return shard_map(
        local_topk,
        mesh=mesh,
        in_specs=(P(None, None), P(None), spec_blk, spec_row, spec_row, spec_row),
        out_specs=(spec_blk, spec_blk),
        check_vma=False,
    )(Aq, nq, B_stack, nb_stack, mask_stack, pos_stack)


@partial(
    jax.jit,
    static_argnames=("mesh", "cfg", "top_k", "col_block", "data_axes"),
)
def stacked_mle_topk_shards(
    Uq: jax.Array,
    Mq: jax.Array,
    U_stack: jax.Array,
    M_stack: jax.Array,
    mask_stack: jax.Array,
    pos_stack: jax.Array,
    *,
    mesh: Mesh,
    cfg: SketchConfig,
    top_k: int,
    col_block: int,
    data_axes: Sequence[str] | str = "data",
):
    """Margin-MLE stage 1 as ONE ``shard_map`` over stacked raw sketches.

    The mle sibling of :func:`stacked_topk_shards`: every shard holds an
    equal-shape block of raw sketch state — ``U_stack`` (S, R, nvec, k) /
    ``M_stack`` (S, R, p-1) placed along ``data_axes`` — and streams the
    (tiny, replicated) query sketch through the engine's scanned strip merge
    with ``pairwise_margin_mle`` strips.  Zero-padded corpus rows are safe:
    the Newton root-solve is elementwise per (query, corpus) pair, so a
    padding row corrupts only its own column, which ``mask_stack`` forces to
    ``+inf`` after the strip estimate.

    Unlike the plain fan this is NOT bitwise stable: segment boundaries
    vanish inside uniform ``col_block`` strips and XLA fuses the per-strip
    Newton solves differently, so values drift by fp noise (~2e-5 relative
    measured) against the exact dispatch answer.  The route therefore only
    serves queries that opted into an ``ApproxContract``, and the caller
    asserts the tolerance against the dispatch reference before admitting an
    operand snapshot (``ShardedSketchIndex._stacked_fan_topk_mle``).

    Returns (vals, positions), both (S, q, k) with k = min(top_k, R),
    sharded over ``data_axes`` on the leading axis.
    """
    from repro.core.pairwise import pairwise_margin_mle
    from repro.engine.reduce import stacked_topk_scan

    data_axes = _tuple(data_axes)
    q = Uq.shape[0]
    _, R, nvec, kdim = U_stack.shape
    if R % col_block != 0:
        raise ValueError(f"stack rows {R} not a multiple of col_block {col_block}")
    n_strips = R // col_block
    k = min(top_k, R)

    def local_topk(uq, mq, u, mm, m, p):
        # squeeze the shard axis: each shard sees one (R, ...) block
        u, mm, m, p = u[0], mm[0], m[0], p[0]
        qs = LpSketch(U=uq, moments=mq)

        def strip_fn(xs):
            us, ms = xs
            return pairwise_margin_mle(qs, LpSketch(U=us, moments=ms), cfg,
                                       clip=True)

        with jax.named_scope("stage1.stacked_mle_topk"):
            vals, pos = stacked_topk_scan(
                strip_fn,
                (u.reshape(n_strips, col_block, nvec, kdim),
                 mm.reshape(n_strips, col_block, mm.shape[-1])),
                m.reshape(n_strips, col_block),
                p.reshape(n_strips, col_block),
                rows=q, top_k=k,
            )
        return vals[None], pos[None]

    spec_u = P(data_axes, None, None, None)
    spec_blk = P(data_axes, None, None)
    spec_row = P(data_axes, None)
    return shard_map(
        local_topk,
        mesh=mesh,
        in_specs=(P(None, None, None), P(None, None), spec_u, spec_blk,
                  spec_row, spec_row),
        out_specs=(P(data_axes, None, None), P(data_axes, None, None)),
        check_vma=False,
    )(Uq, Mq, U_stack, M_stack, mask_stack, pos_stack)


@partial(
    jax.jit,
    static_argnames=("mesh", "relative", "col_block", "backend", "data_axes"),
)
def stacked_threshold_shards(
    Aq: jax.Array,
    nq: jax.Array,
    B_stack: jax.Array,
    nb_stack: jax.Array,
    mask_stack: jax.Array,
    radius: jax.Array,
    *,
    mesh: Mesh,
    relative: bool = False,
    col_block: int,
    backend: str = "xla",
    data_axes: Sequence[str] | str = "data",
):
    """Stage 1 of a sharded threshold scan as ONE ``shard_map``.

    The threshold sibling of :func:`stacked_topk_shards`: every shard holds
    an equal-shape block of packed corpus factors placed along ``data_axes``
    and streams the (replicated) query factors through the engine's scanned
    masked strip criterion concurrently (``engine.reduce.
    stacked_threshold_scan`` — compile O(1) in corpus size, ``radius``
    traced).  Only a per-shard (q, R) bool hit matrix leaves a device —
    1 byte/pair, never a distance strip — and no collective runs at all; the
    host owns the hit → (row, position) extraction and the final merge.

    ``mask_stack`` suppresses tombstones and block padding *after* the strip
    estimate, and the strict float32 ``D < radius`` criterion (relative:
    ``D < radius * (nq_i + nb_j)`` over the marginal p-norms) is evaluated
    exactly as the single-host scan evaluates it, so the surviving pairs are
    pair-for-pair identical.  R must be a multiple of ``col_block``.

    Returns hits (S, q, R) bool, sharded over ``data_axes`` on the leading
    axis.
    """
    from repro.engine.backends import strip_distances
    from repro.engine.reduce import stacked_threshold_scan

    data_axes = _tuple(data_axes)
    q = Aq.shape[0]
    _, R, W = B_stack.shape
    if R % col_block != 0:
        raise ValueError(f"stack rows {R} not a multiple of col_block {col_block}")
    n_strips = R // col_block
    radius = jnp.asarray(radius, jnp.float32)

    def local_hits(aq, nq_, b, nb_, m, r):
        b, nb_, m = b[0], nb_[0], m[0]

        def strip_fn(xs):
            bb, nbb = xs
            return strip_distances(aq, bb, nq_, nbb, backend=backend, clip=True)

        # trace-time annotation only: names this region in jax.profiler /
        # TensorBoard captures, zero runtime cost
        with jax.named_scope("stage1.stacked_threshold"):
            hits = stacked_threshold_scan(
                strip_fn,
                (b.reshape(n_strips, col_block, W),
                 nb_.reshape(n_strips, col_block)),
                m.reshape(n_strips, col_block),
                rows=q, radius=r, relative=relative, nq=nq_,
                nb=nb_.reshape(n_strips, col_block),
            )
        return hits[None]

    spec_blk = P(data_axes, None, None)
    spec_row = P(data_axes, None)
    return shard_map(
        local_hits,
        mesh=mesh,
        in_specs=(P(None, None), P(None), spec_blk, spec_row, spec_row, P()),
        out_specs=spec_blk,
        check_vma=False,
    )(Aq, nq, B_stack, nb_stack, mask_stack, radius)


def knn_sharded(
    queries: LpSketch,
    corpus: LpSketch,
    cfg: SketchConfig,
    mesh: Mesh,
    top_k: int = 10,
    *,
    data_axes: Sequence[str] | str = "data",
    engine_cfg=None,
):
    """Distributed KNN: corpus rows sharded; queries replicated.

    Each shard streams its local strip through the engine's fused top-k
    (col_block columns at a time — the full (q, n_loc) block never
    materializes); the (small) candidate lists are all-gathered and
    re-ranked with ties broken by global index — a standard two-stage
    distributed ANN reduce whose tie-breaking matches the dense path.
    Returns (distances (q, top_k), global indices (q, top_k)).
    """
    from repro.engine import EngineConfig, rerank_topk, streaming_topk  # lazy: avoids cycle

    data_axes = _tuple(data_axes)
    Aq, _, nq = pack_sketch(queries, cfg)
    _, Bc, nc = pack_sketch(corpus, cfg)
    backend, _, col_block = (engine_cfg or EngineConfig()).resolve()

    def local_topk(aq, nq_, bc, nc_):
        nloc = bc.shape[0]
        # stream the local strip through the engine: the (q, nloc) block is
        # consumed col_block columns at a time with a fused candidate merge
        vals, idx = streaming_topk(
            aq, nq_, bc, nc_,
            top_k=min(top_k, nloc), col_block=col_block, backend=backend,
        )
        neg = -vals
        # globalize indices
        shard = jax.lax.axis_index(data_axes[0])
        for ax in data_axes[1:]:
            shard = shard * jax.lax.axis_size(ax) + jax.lax.axis_index(ax)
        gidx = idx + shard * nloc
        # gather candidates from every shard and re-rank; the (value, index)
        # lexsort keeps ties on the dense contract (lowest global index wins)
        # no matter the gather order
        negs, gidxs = neg, gidx
        for ax in data_axes:
            negs = jax.lax.all_gather(negs, ax, axis=1, tiled=True)
            gidxs = jax.lax.all_gather(gidxs, ax, axis=1, tiled=True)
        return rerank_topk(-negs, gidxs, top_k)

    return shard_map(
        local_topk,
        mesh=mesh,
        in_specs=(P(None, None), P(None), P(data_axes, None), P(data_axes)),
        out_specs=(P(None, None), P(None, None)),
        check_vma=False,
    )(Aq, nq, Bc, nc)
