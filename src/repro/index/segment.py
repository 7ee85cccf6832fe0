"""Index segments: a preallocated active segment + immutable sealed blocks.

The index's write path never concatenates: the active segment owns
fixed-shape device buffers (``capacity`` rows of sketch state) and every
ingest batch is written in place with ``lax.dynamic_update_slice`` at a
*traced* offset — one compile per batch shape, O(batch) work per call, no
reallocation.  When the buffer fills, the segment is sealed: trimmed to its
row count, packed once for the plain-estimator query path, and never written
again.

Deletes are tombstones: a host-side ``live`` bitmap per segment.  Queries
mask dead (and, in the active segment, not-yet-written) rows to ``+inf``
*after* the strip estimate, so live-row values stay bit-identical to the
dense path and masked rows can never enter a top-k.  Compaction rewrites a
segment to its live rows only (order preserved — ``jnp.take`` moves bits,
never recomputes them), padding to ``_MIN_SEGMENT_ROWS`` so no segment ever
presents a width-1 strip (which XLA lowers as a GEMV with a different
K-accumulation order than the GEMM columns every other path uses).
"""

from __future__ import annotations

import itertools
from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.pairwise import pack_right
from repro.core.sketch import LpSketch, SketchConfig
from repro.obs.metrics import REGISTRY

__all__ = [
    "ActiveSegment",
    "SealedSegment",
    "SketchReservoir",
    "pack_shard_stack",
    "pack_shard_sketch_stack",
    "shard_stack_live",
    "packed_stack_width",
]

# never present a 1-row segment to the engine: a (n, K) x (K, 1) strip
# lowers as GEMV, breaking the engine's bit-for-bit contract with dense
_MIN_SEGMENT_ROWS = 2

# process-monotonic sealed-segment identity.  Cache keys built from ``id()``
# are unsound: CPython reuses a freed segment's id for the next same-sized
# allocation, so a snapshot cache keyed on object ids can match stacks built
# from segments that no longer exist.  ``uid`` never repeats in a process.
_SEGMENT_UIDS = itertools.count()

# per-segment tombstone delta log length: deltas beyond this fall back to a
# full mask rebuild (the log exists so steady delete traffic stays an O(batch)
# device scatter, not so an unbounded history accumulates)
_TOMBSTONE_LOG_MAX = 64

# trims are the event that downgrades the sharded index's O(deletes) device
# mask scatter to a full host rebuild; counting them tells an operator when
# delete batches are outrunning the delta log
_LOG_TRIMS = REGISTRY.counter(
    "segment.tombstone_log_trims",
    "tombstone delta-log entries dropped (forces a full mask rebuild on the "
    "next stacked-mask refresh)")


@partial(jax.jit, donate_argnums=(0, 1))
def _write_rows(U_buf, M_buf, U_new, M_new, offset):
    """In-place batch write at a traced row offset (compile-once per batch
    shape; donated buffers, so no reallocation on backends with donation)."""
    U_buf = jax.lax.dynamic_update_slice(U_buf, U_new, (offset, 0, 0))
    M_buf = jax.lax.dynamic_update_slice(M_buf, M_new, (offset, 0))
    return U_buf, M_buf


@partial(jax.jit, donate_argnums=(0, 1))
def _scatter_rows(U_buf, M_buf, U_new, M_new, idx):
    """Ring-buffer write: rows land at (possibly wrapping) slot indices."""
    return U_buf.at[idx].set(U_new), M_buf.at[idx].set(M_new)


def _pad_rows(sk: LpSketch, n_pad: int) -> LpSketch:
    if n_pad <= 0:
        return sk
    U = jnp.concatenate(
        [sk.U, jnp.zeros((n_pad, *sk.U.shape[1:]), sk.U.dtype)], axis=0
    )
    M = jnp.concatenate(
        [sk.moments, jnp.zeros((n_pad, sk.moments.shape[1]), sk.moments.dtype)],
        axis=0,
    )
    return LpSketch(U=U, moments=M)


class SealedSegment:
    """An immutable block of sketched rows + tombstone bitmap.

    Packed right factors for the plain estimator are computed once at seal
    time and cached; the device-side live mask is cached until a delete
    invalidates it.
    """

    def __init__(self, sketch: LpSketch, row_ids: np.ndarray,
                 live: Optional[np.ndarray] = None):
        n = sketch.n
        self.sketch = sketch
        self.row_ids = np.asarray(row_ids, np.int64)
        if self.row_ids.shape != (n,):
            raise ValueError(f"row_ids must be ({n},), got {self.row_ids.shape}")
        self.live = (np.ones(n, bool) if live is None
                     else np.asarray(live, bool).copy())
        self.uid = next(_SEGMENT_UIDS)  # process-monotonic, never reused
        self.shard = None     # placement tag (set by sharded indexes)
        self.live_version = 0  # bumped on every tombstone write (mask caches)
        self._packed = None   # (B, nb) right factors, built lazily per cfg
        self._mask_dev = None
        self._live_count = int(self.live.sum())
        self._live_count_version = 0
        # (version, local indices) per tombstone write, so device-resident
        # mask caches can scatter just the flipped rows instead of rebuilding
        self._tombstone_log: list = []
        self._log_floor = 0  # versions <= floor are no longer in the log

    @property
    def n(self) -> int:
        return self.sketch.n

    @property
    def live_count(self) -> int:
        """Cached per tombstone version: the compaction policy consults this
        on every write batch, and an O(n) bitmap scan per segment per write
        (under the index lock) would make the write path O(corpus)."""
        if self._live_count_version != self.live_version:
            self._live_count = int(self.live.sum())
            self._live_count_version = self.live_version
        return self._live_count

    @property
    def live_fraction(self) -> float:
        return self.live_count / max(self.n, 1)

    def delete_local(self, local_idx) -> None:
        self.live[local_idx] = False
        self.live_version += 1
        self._mask_dev = None
        self._tombstone_log.append(
            (self.live_version,
             np.atleast_1d(np.asarray(local_idx, np.int64)).copy()))
        if len(self._tombstone_log) > _TOMBSTONE_LOG_MAX:
            dropped_version, _ = self._tombstone_log.pop(0)
            self._log_floor = dropped_version
            _LOG_TRIMS.inc()

    def tombstones_since(self, version: int) -> Optional[np.ndarray]:
        """Local row indices tombstoned after ``version``, or None when the
        delta is no longer reconstructible (log trimmed, or the bitmap was
        rewritten wholesale) and the caller must rebuild its mask."""
        if version == self.live_version:
            return np.zeros(0, np.int64)
        if version < self._log_floor:
            return None
        out = [idx for v, idx in self._tombstone_log if v > version]
        return np.concatenate(out) if out else np.zeros(0, np.int64)

    def packed(self, cfg: SketchConfig):
        """(B, nb): cached right factor + marginal norms for plain strips."""
        if self._packed is None:
            self._packed = pack_right(self.sketch, cfg)
        return self._packed

    def mask(self) -> jax.Array:
        """(n,) bool device mask — True where the row is live."""
        if self._mask_dev is None:
            self._mask_dev = jnp.asarray(self.live)
        return self._mask_dev

    def compacted(self, live: Optional[np.ndarray] = None) -> "SealedSegment":
        """Live rows only, order preserved, padded (dead) to the engine's
        minimum strip width.  Bits of live rows are moved, never recomputed,
        so query results are identical pre/post compaction.

        ``live`` overrides the segment's current bitmap with a snapshot —
        the background compactor builds replacements from a snapshot taken
        off the query path and replays any tombstones that landed later at
        swap time."""
        keep = np.flatnonzero(self.live if live is None else live)
        n_pad = max(_MIN_SEGMENT_ROWS - len(keep), 0)
        idx = jnp.asarray(keep, jnp.int32)
        sk = LpSketch(
            U=jnp.take(self.sketch.U, idx, axis=0),
            moments=jnp.take(self.sketch.moments, idx, axis=0),
        )
        sk = _pad_rows(sk, n_pad)
        row_ids = np.concatenate([self.row_ids[keep], np.full(n_pad, -1, np.int64)])
        live_out = np.concatenate([np.ones(len(keep), bool), np.zeros(n_pad, bool)])
        return SealedSegment(sk, row_ids, live_out)


class ActiveSegment:
    """The write head: fixed-capacity device buffers filled left to right.

    Queries see the *full* capacity buffer (shape never changes, so the
    query path compiles once) with rows past ``size`` masked dead alongside
    tombstones.
    """

    def __init__(self, cfg: SketchConfig, capacity: int):
        if capacity < _MIN_SEGMENT_ROWS:
            raise ValueError(f"capacity must be >= {_MIN_SEGMENT_ROWS}")
        self.cfg = cfg
        self.capacity = capacity
        self.U = jnp.zeros((capacity, cfg.vectors_per_row, cfg.k),
                           cfg.projection.dtype)
        self.moments = jnp.zeros((capacity, cfg.num_moments), jnp.float32)
        self.row_ids = np.full(capacity, -1, np.int64)
        self.live = np.zeros(capacity, bool)
        self.size = 0
        self._mask_dev = None

    @property
    def remaining(self) -> int:
        return self.capacity - self.size

    @property
    def live_count(self) -> int:
        return int(self.live.sum())

    def append(self, sk: LpSketch, row_ids: np.ndarray) -> None:
        b = sk.n
        if b > self.remaining:
            raise ValueError(f"batch of {b} exceeds remaining {self.remaining}")
        self.U, self.moments = _write_rows(
            self.U, self.moments, sk.U, sk.moments, jnp.int32(self.size)
        )
        self.row_ids[self.size:self.size + b] = row_ids
        self.live[self.size:self.size + b] = True
        self.size += b
        self._mask_dev = None

    def delete_local(self, local_idx) -> None:
        self.live[local_idx] = False
        self._mask_dev = None

    def mask(self) -> jax.Array:
        if self._mask_dev is None:
            self._mask_dev = jnp.asarray(self.live)
        return self._mask_dev

    def as_sketch(self) -> LpSketch:
        """Full-capacity view (fixed shape; dead slots are masked at query)."""
        return LpSketch(U=self.U, moments=self.moments)

    def seal(self) -> SealedSegment:
        """Freeze: trim to the written rows (one-time shape) and hand off."""
        n = max(self.size, _MIN_SEGMENT_ROWS)
        sk = LpSketch(U=self.U[:n], moments=self.moments[:n])
        return SealedSegment(sk, self.row_ids[:n].copy(), self.live[:n].copy())


# ---------------------------------------------------------------------------
# Stacked packing: equal-shape per-shard blocks for the shard_map stage-1 fan
# ---------------------------------------------------------------------------


def packed_stack_width(cfg: SketchConfig) -> int:
    """Column count of ``pack_sketch``'s packed factors: one k-wide slab per
    interaction order (needed to shape all-padding blocks on empty shards)."""
    from repro.core.decomposition import interaction_orders

    return len(interaction_orders(cfg.p)) * cfg.k


def pack_shard_stack(group, rows: int, cfg: SketchConfig, device=None):
    """Pack one shard's sealed segments into a single equal-shape block.

    ``group`` is ``[(global position base, SealedSegment), ...]`` in ingest
    order; ``rows`` is the fleet-wide uniform block height (>= this shard's
    total rows, a multiple of the engine's col_block).  Segments' cached
    packed factors are concatenated on the shard's own device and zero-padded
    to ``rows`` — padding never surfaces because the stacked fan masks it to
    ``+inf`` — so every shard presents the identical SPMD operand shape.

    Returns ``(B (rows, W), nb (rows,))`` committed to ``device`` plus the
    host-side position map ``pos (rows,) int32`` (global position per row,
    the int32 sentinel on padding).  The live mask is deliberately NOT built
    here: factors change only when the segment list changes, tombstones on
    every delete — see :func:`shard_stack_live`.
    """
    W = packed_stack_width(cfg)
    sentinel = np.iinfo(np.int32).max
    pos = np.full(rows, sentinel, np.int32)
    parts_B, parts_nb, r0 = [], [], 0
    for base, seg in group:
        B, nb = seg.packed(cfg)
        parts_B.append(B)
        parts_nb.append(nb)
        pos[r0:r0 + seg.n] = base + np.arange(seg.n, dtype=np.int32)
        r0 += seg.n
    if r0 > rows:
        raise ValueError(f"shard holds {r0} rows > stack height {rows}")
    n_pad = rows - r0
    if not parts_B:
        dtype = jnp.dtype(cfg.projection.dtype)
        B_blk = jnp.zeros((rows, W), dtype)
        nb_blk = jnp.zeros((rows,), jnp.float32)
    else:
        if n_pad:
            parts_B.append(jnp.zeros((n_pad, W), parts_B[0].dtype))
            parts_nb.append(jnp.zeros((n_pad,), parts_nb[0].dtype))
        B_blk = jnp.concatenate(parts_B, axis=0)
        nb_blk = jnp.concatenate(parts_nb, axis=0)
    if device is not None:
        B_blk = jax.device_put(B_blk, device)
        nb_blk = jax.device_put(nb_blk, device)
    return B_blk, nb_blk, pos


def pack_shard_sketch_stack(group, rows: int, cfg: SketchConfig, device=None):
    """Stack one shard's raw sealed sketches into equal-shape blocks.

    The margin-MLE sibling of :func:`pack_shard_stack`: mle strips consume
    the sketch itself (per-row projections ``U`` and marginal ``moments``),
    not the plain packed factors, so the stacked mle fan needs per-shard
    ``(rows, nvec, k)`` / ``(rows, p-1)`` blocks zero-padded to the
    fleet-wide uniform height.  Zero padding is safe for the elementwise
    Newton solve — a garbage estimate stays confined to its own (masked)
    column and the stacked fan forces it to ``+inf`` after the strip.

    Returns ``(U_blk (rows, nvec, k), M_blk (rows, p-1))`` committed to
    ``device``.  Positions and the live mask are shared with the plain
    stack (same segments, same stack order), so they are not rebuilt here.
    """
    nvec = cfg.vectors_per_row
    parts_U, parts_M, r0 = [], [], 0
    for _base, seg in group:
        parts_U.append(seg.sketch.U)
        parts_M.append(seg.sketch.moments)
        r0 += seg.n
    if r0 > rows:
        raise ValueError(f"shard holds {r0} rows > stack height {rows}")
    n_pad = rows - r0
    if not parts_U:
        U_blk = jnp.zeros((rows, nvec, cfg.k), jnp.dtype(cfg.projection.dtype))
        M_blk = jnp.zeros((rows, cfg.num_moments), jnp.float32)
    else:
        if n_pad:
            parts_U.append(jnp.zeros((n_pad,) + parts_U[0].shape[1:],
                                     parts_U[0].dtype))
            parts_M.append(jnp.zeros((n_pad,) + parts_M[0].shape[1:],
                                     parts_M[0].dtype))
        U_blk = jnp.concatenate(parts_U, axis=0)
        M_blk = jnp.concatenate(parts_M, axis=0)
    if device is not None:
        U_blk = jax.device_put(U_blk, device)
        M_blk = jax.device_put(M_blk, device)
    return U_blk, M_blk


def shard_stack_live(group, rows: int) -> np.ndarray:
    """(rows,) host live mask for one shard's stacked block: per-segment
    tombstone bitmaps in stack order, False on block padding."""
    live = np.zeros(rows, bool)
    r0 = 0
    for _base, seg in group:
        live[r0:r0 + seg.n] = seg.live
        r0 += seg.n
    return live


class SketchReservoir:
    """Fixed-capacity FIFO ring of sketched rows (dedup's reservoir).

    Admission overwrites the oldest slots in place via a jitted scatter —
    O(batch) per admit at any reservoir size, vs. the old grow-and-slice
    concat which reallocated the whole reservoir every batch.
    """

    def __init__(self, cfg: SketchConfig, capacity: int):
        if capacity < _MIN_SEGMENT_ROWS:
            raise ValueError(f"capacity must be >= {_MIN_SEGMENT_ROWS}")
        self.cfg = cfg
        self.capacity = capacity
        self.U = jnp.zeros((capacity, cfg.vectors_per_row, cfg.k),
                           cfg.projection.dtype)
        self.moments = jnp.zeros((capacity, cfg.num_moments), jnp.float32)
        self.count = 0  # total rows ever admitted

    @property
    def size(self) -> int:
        return min(self.count, self.capacity)

    def admit(self, sk: LpSketch) -> None:
        b = sk.n
        if b == 0:
            return
        if b > self.capacity:  # only the newest `capacity` rows can survive
            sk = LpSketch(U=sk.U[-self.capacity:],
                          moments=sk.moments[-self.capacity:])
            self.count += b - self.capacity
            b = self.capacity
        idx = (self.count + jnp.arange(b, dtype=jnp.int32)) % self.capacity
        self.U, self.moments = _scatter_rows(
            self.U, self.moments, sk.U, sk.moments, idx
        )
        self.count += b

    def view(self) -> Tuple[LpSketch, np.ndarray]:
        """(full-buffer sketch, live mask) — fixed shapes at any fill."""
        live = np.arange(self.capacity) < self.size
        return LpSketch(U=self.U, moments=self.moments), live
