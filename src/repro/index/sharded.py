"""``ShardedSketchIndex`` — sealed segments spread over a device mesh.

The paper's setting is a matrix A too large for one machine; PR 2's
``SketchIndex`` shrank A to O(nk) sketch state but still pinned every segment
to a single host.  This layer places each sealed segment on a shard of a
device mesh (round-robin over the mesh's data axis) and answers queries with
the same two-stage reduce ``knn_sharded`` uses:

  stage 1  every shard streams *its* segments through the engine's strip
           machinery (plain packed-matmul or margin-MLE strips, tombstones
           masked to +inf) and keeps a per-shard candidate list of width
           min(top_k, shard rows) — only (q, k) candidates leave a shard,
           never a distance strip;
  stage 2  the per-shard lists are gathered and re-ranked by (value, global
           position) — ``rerank_topk``'s lexsort — so equal distances
           resolve to the earliest-ingested live row exactly as the
           single-host fan (and the dense path) resolve them, even though
           round-robin placement makes shard order differ from position
           order.

Values are never recomputed between stages, strips are tiled per segment
exactly as the single-host fan tiles them, and the merge contract above pins
ties: results are **bit-identical** to ``SketchIndex`` over the same live
rows, which the conformance suite (tests/test_conformance.py) gates.

The active (write-head) segment stays on the process-local default device —
ingest latency never pays a cross-device hop — and joins the fan as one more
candidate source.  Background compaction (``compact_async``) rebuilds a
shard's segments on that same shard and swaps them in under the index
generation flip; ``load`` re-spreads a stored index over whatever mesh the
restoring process was launched with via per-segment ``device_put``.

Stage 1 runs in one of two modes, for BOTH reduces (top-k and threshold):

  parallel (the default whenever a mesh is available)  each shard's sealed
      segments are packed into one equal-shape block — concatenated packed
      factors, zero-padded to a fleet-wide uniform height, padding and
      tombstones live-masked off — placed along the mesh's ``data`` axis,
      and ALL shards fold their strips concurrently inside a single
      ``shard_map`` (``core.distributed.stacked_topk_shards`` /
      ``stacked_threshold_shards``); stage-1 wall-clock is the slowest
      shard, not the sum.  Plain packed-matmul strips are bitwise invariant
      to the re-tiling (the conformance suite's strip-invariance property),
      so results stay bit-identical; threshold hits leave a shard as a bool
      bitmap, never a distance.  Tombstone deltas refresh the stacked live
      mask device-side (a per-shard scatter of just the flipped rows).
  dispatch (fallback)  the per-segment async-dispatch fan below — used when
      no usable mesh exists (duplicate device lists), and by default for the
      ``mle`` estimator, whose per-strip Newton solves are NOT bitwise stable
      under XLA fusion contexts; keeping mle on the exact single-host strip
      programs is what keeps it bit-identical.  Passing
      ``approx_ok=ApproxContract(...)`` opts an mle top-k query onto the
      stacked fan, tolerance-gated per operand snapshot against the exact
      dispatch answer.

Which mode serves a given query is decided by ``repro.index.planner``: every
query computes an explicit ``QueryPlan`` (route + fallback chain + expected
cost) and the executors below walk ``plan.chain`` until a route serves —
there are no per-path estimator branches here anymore.

Because every shard's stacked block pads to the tallest shard, a skewed
shard inflates the whole fleet's stage-1 work; ``rebalance()`` (and its
``RebalancePolicy`` auto-trigger) migrates whole sealed segments between
shards to level stacked heights — ``device_put`` only, answers unchanged.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Callable, List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro import obs
from repro.core import registry
from repro.core.distributed import (
    _tuple as _axes_tuple,
    mesh_shard_devices,
    stacked_mle_topk_shards,
    stacked_threshold_shards,
    stacked_topk_shards,
)
from repro.core.sketch import LpSketch, SketchConfig
from repro.engine import EngineConfig
from repro.engine.reduce import rerank_topk, within_tolerance
from repro.obs.metrics import REGISTRY

from .planner import STAGE1_LABEL, ApproxContract, QueryPlan
from .query import (
    _IDX_SENTINEL,
    _check_top_k,
    _finite_k,
    _fold_segment_topk,
    _merge_threshold_hits,
    _pack_query,
    _segment_rows,
    _segment_threshold_hits,
)
from .segment import (
    ActiveSegment,
    SealedSegment,
    pack_shard_sketch_stack,
    pack_shard_stack,
    packed_stack_width,
    shard_stack_live,
)
from .service import CompactionPolicy, IndexConfig, SketchIndex

__all__ = ["ShardedSketchIndex", "RebalancePolicy", "sharded_fan_topk",
           "sharded_threshold_scan"]

Segment = Union[ActiveSegment, SealedSegment]

# process-global serving/maintenance counters, resolved once at import so
# the per-query hot path never takes the registry lock.  Counters are always
# live; spans/histograms cost nothing until obs.enable().
_STAGE1_PARALLEL = REGISTRY.counter(
    "index.stage1_parallel", "stage-1 fans served by the stacked shard_map")
_STAGE1_DISPATCH = REGISTRY.counter(
    "index.stage1_dispatch", "stage-1 fans served by the dispatch fallback")
_FAN_MESH_DECLINED = REGISTRY.counter(
    "index.stacked_fan_declined",
    "sharded indexes built without a stacked fan because the mesh and the "
    "shard device list disagree")
_STACK_HITS = REGISTRY.counter(
    "index.stack_cache_hits", "stacked-operand cache hits")
_STACK_MISSES = REGISTRY.counter(
    "index.stack_cache_misses", "stacked-operand cache (re)builds")
_MASK_SCATTERS = REGISTRY.counter(
    "index.mask_scatter_updates",
    "device-side tombstone-delta scatters into resident masks")
_MASK_REBUILDS = REGISTRY.counter(
    "index.mask_full_builds",
    "full host live-mask rebuilds (fresh stack or trimmed delta log)")
_REBALANCE_PLANS = REGISTRY.counter(
    "index.rebalance_plans", "rebalance passes that computed a plan")
_REBALANCE_COMMITS = REGISTRY.counter(
    "index.rebalance_commits", "rebalance passes that moved >= 1 segment")
_REBALANCE_DECLINES = REGISTRY.counter(
    "index.rebalance_declines",
    "rebalance passes declined (skew below trigger, no-progress plan, or a "
    "pass already in flight)")
_REBALANCE_MOVED = REGISTRY.counter(
    "index.rebalance_segments_moved", "segments migrated between shards")


@dataclasses.dataclass(frozen=True)
class RebalancePolicy:
    """Scheduling policy that drives :meth:`ShardedSketchIndex.rebalance`.

    The stacked stage-1 fan pads every shard's block to the tallest shard's
    height, so one skewed shard inflates every block in the fleet — the
    exact failure mode heavy delete traffic (then compaction) on one shard
    produces.  ``maybe_rebalance()`` (hooked after every delete/ingest batch
    and after every compaction swap when ``auto`` is set) migrates segments
    iff

      * the stacked-height skew ``max/mean`` across shards strictly exceeds
        ``skew_trigger``,
      * at least ``min_interval_s`` elapsed since the last rebalance pass
        started (manual ``rebalance()`` calls arm the limiter too), and
      * migrating actually changes some segment's placement.

    Attributes:
      skew_trigger: max/mean physical stacked rows per shard above which a
        migration pass is worth scheduling.
      min_interval_s: minimum seconds between pass starts — keeps a delete
        storm from thrashing segments between shards.
      auto: hook the check into ``delete``/``ingest``/compaction-swap
        (False = only explicit ``maybe_rebalance()`` calls consult it).
      clock: monotonic time source (injectable for deterministic tests).
    """

    skew_trigger: float = 1.5
    min_interval_s: float = 60.0
    auto: bool = True
    clock: Callable[[], float] = time.monotonic

    def __post_init__(self):
        if self.skew_trigger < 1.0:
            raise ValueError("skew_trigger must be >= 1 (max/mean ratio)")
        if self.min_interval_s < 0:
            raise ValueError("min_interval_s must be >= 0")


def _query_on(dev, qsk: LpSketch, q_packed, spec: registry.EstimatorSpec):
    """Move the (tiny) query-side factors onto one shard's device."""
    if dev is None:
        return qsk, q_packed
    if spec.uses_packed:
        Aq, nq = q_packed
        return qsk, (jax.device_put(Aq, dev), jax.device_put(nq, dev))
    qs = LpSketch(U=jax.device_put(qsk.U, dev),
                  moments=jax.device_put(qsk.moments, dev))
    return qs, q_packed


def _group_by_shard(segments: Sequence[Segment], n_shards: int):
    """[(shard device index | None, [(global base, segment), ...])] with the
    active segment (shard None) last; bases follow global ingest order."""
    groups: List[List[Tuple[int, Segment]]] = [[] for _ in range(n_shards)]
    local: List[Tuple[int, Segment]] = []
    base = 0
    for seg in segments:
        shard = getattr(seg, "shard", None)
        if isinstance(seg, ActiveSegment) or shard is None:
            local.append((base, seg))
        else:
            groups[shard].append((base, seg))
        base += _segment_rows(seg)
    out = [(s, grp) for s, grp in enumerate(groups) if grp]
    if local:
        out.append((None, local))
    return out, base


def _shard_candidates(qsk, q_packed, group, cfg, spec, backend,
                      col_block, top_k, q):
    """Stage 1: one shard's candidate list in global-position space.

    Runs the exact per-segment fold the single-host fan runs
    (``_fold_segment_topk``), restricted to this shard's segments — the
    per-segment candidates are identical by construction."""
    shard_rows = sum(_segment_rows(seg) for _, seg in group)
    k = min(top_k, shard_rows)
    vals = jnp.full((q, k), jnp.inf, jnp.float32)
    idx = jnp.full((q, k), _IDX_SENTINEL, jnp.int32)
    for base, seg in group:
        vals, idx = _fold_segment_topk(vals, idx, qsk, q_packed, seg, cfg,
                                       spec, backend, col_block, base, k)
    return vals, idx


def _ids_for_positions(segments, pos: np.ndarray) -> np.ndarray:
    """Translate global positions -> stable row ids in O(k log S + S).

    The fans used to concatenate every segment's row_ids into one corpus-
    sized map per query; only the (q, k) result positions ever need
    translating, so bucket them by segment instead."""
    bases = np.cumsum([0] + [_segment_rows(s) for s in segments])
    out = np.empty(pos.shape, np.int64)
    seg_of = np.searchsorted(bases, pos, side="right") - 1
    for si in np.unique(seg_of):
        m = seg_of == si
        out[m] = segments[si].row_ids[pos[m] - bases[si]]
    return out


class _StackedOperands:
    """Device-resident stage-1 operand stacks for one sealed-segment snapshot.

    Factors (``B``/``nb``/``pos``) are immutable for a given segment list and
    rebuild only when the list changes (seal / compaction swap / rebalance /
    load) — detected by ``key``, built from each segment's process-monotonic
    ``uid`` (NEVER ``id()``: CPython reuses a freed segment's id, so an id
    key could match stacks packed from segments that no longer exist).  The
    live ``mask`` additionally tracks per-segment tombstone versions; a
    delete refreshes the (cheap, bool) mask in place — a per-shard device
    scatter of just the flipped rows — and never touches the factor stacks.
    ``pos_host`` mirrors ``pos`` for the threshold fan's host-side
    hit → global-position extraction."""

    __slots__ = ("key", "groups", "rows", "col_block", "B", "nb", "pos",
                 "pos_host", "mask", "mask_versions", "mask_full_builds",
                 "mask_scatter_updates", "Usk", "Msk")

    def __init__(self, key, groups, rows, col_block, B, nb, pos, pos_host):
        self.key = key
        self.groups = groups
        self.rows = rows
        self.col_block = col_block
        self.B, self.nb, self.pos = B, nb, pos
        self.pos_host = pos_host
        self.mask = None
        self.mask_versions = None
        self.mask_full_builds = 0
        self.mask_scatter_updates = 0
        # raw-sketch stacks for the approx mle fan, built lazily on first use
        # (most corpora never opt in) and sharing this snapshot's lifetime
        self.Usk = None
        self.Msk = None


def _build_stacked_operands(shard_groups, n_shards, mesh, devices,
                            cfg: SketchConfig, col_block: int, data_axes,
                            key) -> _StackedOperands:
    """Equal-shape per-shard blocks, assembled in place on the mesh.

    Each shard's block is packed on its own device (``pack_shard_stack``) and
    the global (S, rows, W) stacks are stitched from those single-device
    blocks — the corpus factors never round-trip through the host."""
    dax = _axes_tuple(data_axes)
    rows = max(sum(_segment_rows(seg) for _b, seg in g) for _s, g in shard_groups)
    rows = max(rows, col_block)
    rows = -(-rows // col_block) * col_block  # whole strips only
    group_of = dict(shard_groups)
    W = packed_stack_width(cfg)
    parts_B, parts_nb = [], []
    pos = np.empty((n_shards, rows), np.int32)
    for s in range(n_shards):
        B_blk, nb_blk, pos_blk = pack_shard_stack(
            group_of.get(s, []), rows, cfg, devices[s])
        parts_B.append(B_blk[None])
        parts_nb.append(nb_blk[None])
        pos[s] = pos_blk
    sh_blk = NamedSharding(mesh, P(dax, None, None))
    sh_row = NamedSharding(mesh, P(dax, None))
    B = jax.make_array_from_single_device_arrays(
        (n_shards, rows, W), sh_blk, parts_B)
    nb = jax.make_array_from_single_device_arrays(
        (n_shards, rows), sh_row, parts_nb)
    return _StackedOperands(key, shard_groups, rows, col_block, B, nb,
                            jax.device_put(pos, sh_row), pos)


def sharded_fan_topk(
    qsk: LpSketch,
    segments: Sequence[Segment],
    cfg: SketchConfig,
    devices: Sequence,
    *,
    top_k: int,
    estimator: str = registry.DEFAULT_ESTIMATOR,
    engine: Optional[EngineConfig] = None,
) -> Tuple[jax.Array, np.ndarray]:
    """Two-stage top-k fan over device-placed segments.

    Bit-identical (values and tie-broken ids) to ``fan_topk`` over the same
    segments: stage 1 keeps raw strip values, stage 2's (value, position)
    lexsort reproduces the dense tie-break regardless of placement."""
    spec = registry.resolve(estimator, p=cfg.p,
                            projection=cfg.projection.family)
    _check_top_k(top_k)
    backend, _, col_block = (engine or EngineConfig()).resolve()
    q = qsk.n
    n_live = sum(seg.live_count for seg in segments)
    k_out = min(top_k, n_live)
    if k_out == 0:
        return (jnp.zeros((q, 0), jnp.float32), np.zeros((q, 0), np.int64))

    groups, total = _group_by_shard(segments, len(devices))
    q_packed = _pack_query(qsk, cfg, spec)

    # dispatch every shard's stage-1 work before gathering any of it: jax
    # dispatch is async, so the shards compute concurrently and stage-1
    # wall-clock is the slowest shard, not the sum
    with obs.span("index.fan.stage1", metric="index.stage1_dispatch_ms",
                  mode="dispatch", shards=len(groups)):
        pending = []
        for shard, group in groups:
            dev = devices[shard] if shard is not None else None
            with obs.span("index.fan.shard", shard=shard,
                          segments=len(group)):
                qs, qp = _query_on(dev, qsk, q_packed, spec)
                pending.append(_shard_candidates(qs, qp, group, cfg,
                                                 spec, backend,
                                                 col_block, top_k, q))

        # only the (q, k) candidate lists cross the shard boundary
        all_vals = [np.asarray(jax.device_get(v)) for v, _ in pending]
        all_idx = [np.asarray(jax.device_get(i)) for _, i in pending]
    with obs.span("index.fan.stage2"):
        cat_vals = np.concatenate(all_vals, axis=1)
        k_out = _finite_k(cat_vals, k_out)
        vals, idx = rerank_topk(cat_vals, np.concatenate(all_idx, axis=1),
                                k_out)
        return vals, _ids_for_positions(segments, np.asarray(idx))


def sharded_threshold_scan(
    qsk: LpSketch,
    segments: Sequence[Segment],
    cfg: SketchConfig,
    devices: Sequence,
    *,
    radius: float,
    relative: bool = False,
    estimator: str = registry.DEFAULT_ESTIMATOR,
    engine: Optional[EngineConfig] = None,
) -> Tuple[np.ndarray, np.ndarray]:
    """(query_rows, row_ids) with D < radius over device-placed segments.

    Per-shard strips leave only hit pairs; the final (query, id) lexsort is
    the same order ``threshold_scan`` (and the engine's row-major dense
    contract) produces, so results are pair-for-pair identical."""
    spec = registry.resolve(estimator, p=cfg.p,
                            projection=cfg.projection.family)
    backend, _, col_block = (engine or EngineConfig()).resolve()
    groups, _ = _group_by_shard(segments, len(devices))
    q_packed = _pack_query(qsk, cfg, spec)
    nq_h = np.asarray(qsk.norm_pp(cfg.p))

    rows_out, ids_out = [], []
    with obs.span("index.fan.stage1", metric="index.stage1_dispatch_ms",
                  mode="dispatch", shards=len(groups)):
        for shard, group in groups:
            dev = devices[shard] if shard is not None else None
            with obs.span("index.fan.shard", shard=shard,
                          segments=len(group)):
                qs, qp = _query_on(dev, qsk, q_packed, spec)
                for _base, seg in group:
                    rr, ii = _segment_threshold_hits(qs, qp, seg, cfg,
                                                     spec, backend,
                                                     col_block, nq_h,
                                                     radius, relative)
                    rows_out.extend(rr)
                    ids_out.extend(ii)
    with obs.span("index.fan.stage2"):
        return _merge_threshold_hits(rows_out, ids_out)


class ShardedSketchIndex(SketchIndex):
    """A ``SketchIndex`` whose sealed segments live across a device mesh.

    Construction takes either a ``mesh`` (the shard list is the mesh's data
    axis, via ``mesh_shard_devices``) or an explicit ``devices`` list; with a
    distinct explicit device list a serving mesh is built automatically, so
    the restore path keeps the parallel stage-1 fan.  The full lifecycle —
    ingest, delete, compact/compact_async, save, load — is inherited;
    placement rides on the base class's ``_place_segment`` hook, so sealing,
    background-compaction swaps, and reload all land segments on their shard
    without special cases.
    """

    def __init__(self, cfg: SketchConfig, *, seed: int = 0,
                 index_cfg: Optional[IndexConfig] = None,
                 engine: Optional[EngineConfig] = None,
                 mesh=None, devices: Optional[Sequence] = None,
                 data_axes="data", policy: Optional[CompactionPolicy] = None,
                 rebalance_policy: Optional[RebalancePolicy] = None):
        if devices is None:
            devices = (mesh_shard_devices(mesh, data_axes)
                       if mesh is not None else jax.devices())
        self.devices = list(devices)
        if not self.devices:
            raise ValueError("sharded index needs at least one device")
        # normalized to a tuple once: downstream it feeds a static jit
        # argument (hashability) and PartitionSpecs alike
        self.data_axes = _axes_tuple(data_axes)
        if mesh is None and len(set(self.devices)) == len(self.devices):
            # distinct explicit devices: rebuild the serving mesh so the
            # stacked shard_map fan survives restore-by-device-list
            from repro.launch.mesh import make_serving_mesh
            mesh = make_serving_mesh(len(self.devices), devices=self.devices)
        self.mesh = mesh
        # the stacked fan needs shard i of the stack and segment placement to
        # agree on a physical device; a mesh that disagrees with the explicit
        # device list (or duplicate fake shards) leaves only the dispatch fan,
        # and says why in stats()["stacked_fan_declined"] and a counter
        self._fan_mesh = None
        self._fan_declined: Optional[str] = None
        if mesh is None:
            self._fan_declined = "no mesh: duplicate shard devices"
        else:
            try:
                mesh_devs = list(mesh_shard_devices(mesh, data_axes))
            except (KeyError, ValueError) as e:
                self._fan_declined = f"mesh has no data axes {data_axes!r}: {e}"
            else:
                if mesh_devs == self.devices:
                    self._fan_mesh = mesh
                else:
                    self._fan_declined = (
                        "mesh data-axis devices differ from the shard devices")
        if self._fan_declined is not None:
            _FAN_MESH_DECLINED.inc()
        self._stack: Optional[_StackedOperands] = None
        self._last_stage1: Optional[str] = None  # mode of the last query
        # last OBSERVED stage-1 mode per estimator — what stats() reports
        # once a query has actually run (predictions only fill the gap)
        self._last_route: dict = {}
        self.rebalance_policy = rebalance_policy
        self._last_rebalance_start: Optional[float] = None
        self._rebalance_active = False  # one transfer pass at a time
        self.auto_rebalances = 0  # policy-triggered passes, for observability
        super().__init__(cfg, seed=seed, index_cfg=index_cfg, engine=engine,
                         policy=policy)

    @property
    def n_shards(self) -> int:
        return len(self.devices)

    def stats(self) -> dict:
        s = super().stats()
        per_shard = [0] * self.n_shards
        rows_per_shard = [0] * self.n_shards
        with self._lock:
            for seg in self.sealed:
                if seg.shard is not None:
                    per_shard[seg.shard] += 1
                    rows_per_shard[seg.shard] += seg.n
        s["shards"] = self.n_shards
        s["segments_per_shard"] = per_shard
        s["rows_per_shard"] = rows_per_shard
        s["shard_skew"] = self._shard_skew(rows_per_shard)
        # per-estimator, last OBSERVED mode — a plain query silently falling
        # back to dispatch (nothing sealed, stale devices) must show up here.
        # Before any query runs, report the planner's prediction instead of
        # guessing from `_fan_mesh` directly.
        s["stage1"] = {
            est: self._last_route.get(est, self._predicted_stage1(est))
            for est in registry.names_for(self.cfg)
        }
        s["stage1"]["last"] = self._last_stage1
        s["stacked_fan_declined"] = self._fan_declined
        s["planner"] = self.planner.stats()
        s["auto_rebalances"] = self.auto_rebalances
        return s

    def _predicted_stage1(self, estimator: str) -> str:
        """Mode a top-k query with this estimator would plan right now
        (read-only: never counts as a planned query)."""
        with self._lock:
            sealed = len(self.sealed)
        plan = self.planner.plan(
            reduce="topk", estimator=estimator, sharded=True,
            mesh_available=self._fan_mesh is not None,
            sealed_segments=sealed, record=False)
        return STAGE1_LABEL[plan.route]

    @staticmethod
    def _shard_skew(rows_per_shard) -> float:
        """max/mean physical stacked rows across shards (1.0 = balanced;
        the stacked fan pads every block to the max, so skew is the factor
        by which one hot shard inflates the whole fleet's stage-1 work)."""
        total = sum(rows_per_shard)
        if total == 0:
            return 1.0
        return max(rows_per_shard) / (total / len(rows_per_shard))

    # ------------------------------------------------------------- placement

    def _segments_changed(self) -> None:
        # drop the stacked stage-1 operands with the segment list they were
        # packed from: in-flight queries keep their own reference, the next
        # plain top-k rebuilds from the new list
        self._stack = None

    def _shard_for_new_segment(self) -> int:
        return len(self.sealed) % self.n_shards

    def _place_segment(self, seg: SealedSegment,
                       shard: Optional[int] = None) -> SealedSegment:
        """Pin a segment's device buffers to its shard.

        ``device_put`` moves bits, never recomputes them, so placement keeps
        the bit-for-bit query contract.  Cached packed factors / masks are
        dropped — they rebuild lazily on the target device."""
        shard = (shard if shard is not None else 0) % self.n_shards
        dev = self.devices[shard]
        seg.sketch = LpSketch(U=jax.device_put(seg.sketch.U, dev),
                              moments=jax.device_put(seg.sketch.moments, dev))
        seg._packed = None
        seg._mask_dev = None
        seg.shard = shard
        return seg

    # ------------------------------------------------------------ rebalance

    def rebalance(self, *, skew_trigger: Optional[float] = None,
                  force: bool = False) -> int:
        """Migrate whole sealed segments between shards to level stacked
        heights; returns how many segments moved.

        The stacked stage-1 fan pads every shard's block to the tallest
        shard, so a skewed shard (heavy deletes then compaction, or lopsided
        restore) inflates every block in the fleet.  When the physical-row
        skew ``max/mean`` strictly exceeds ``skew_trigger`` (or always, with
        ``force=True``), segments are re-placed by a greedy bin-pack on live
        rows — largest segment first onto the currently lightest shard — and
        moved with ``device_put`` (bits move, estimates are never recomputed,
        so query results are bit-for-bit unchanged).

        The pass runs compact_async-style, copy-then-flip: the plan and the
        move list are snapshotted under the index lock, the ``device_put``
        transfers run with the lock RELEASED (sealed sketches are immutable,
        and ``_rebalance_active`` excludes a second concurrent pass — the
        only other writer of a sealed segment's device buffers), then the
        new placements flip in atomically under the lock with one generation
        bump.  Queries keep serving the old placement during the transfers
        and see old or new, never a mix; segments compacted away
        mid-transfer are detected by uid at commit and skipped."""
        if skew_trigger is not None and skew_trigger < 1.0:
            raise ValueError("skew_trigger must be >= 1 (max/mean ratio)")
        with obs.span("index.rebalance",
                      metric="index.rebalance_ms") as sp:
            with self._lock:
                if self._rebalance_active:
                    _REBALANCE_DECLINES.inc()
                    return 0  # a pass is already transferring
                rows_per_shard = [0] * self.n_shards
                for seg in self.sealed:
                    rows_per_shard[(seg.shard or 0) % self.n_shards] += seg.n
                if not force:
                    thr = (skew_trigger if skew_trigger is not None else
                           (self.rebalance_policy.skew_trigger
                            if self.rebalance_policy is not None else 1.5))
                    if self._shard_skew(rows_per_shard) <= thr:
                        _REBALANCE_DECLINES.inc()
                        return 0
                # arm the rate limiter only when a pass actually starts: a
                # declined skew check must never push back the next window
                self._arm_rebalance_limit()
                _REBALANCE_PLANS.inc()
                # greedy bin-pack on live rows: largest first, lightest
                # shard wins; ties resolve by (shard index) then (uid) so
                # the plan is deterministic for a given segment list
                order = sorted(self.sealed,
                               key=lambda g: (-g.live_count, g.uid))
                load = [0] * self.n_shards
                plan = {}
                for seg in order:
                    tgt = min(range(self.n_shards),
                              key=lambda s: (load[s], s))
                    load[tgt] += max(seg.live_count, 1)
                    plan[seg.uid] = tgt
                # commit only if the plan strictly improves the PHYSICAL
                # height skew (what pads the stacked blocks): live counts
                # and physical rows diverge on un-compacted tombstones, and
                # a no-progress migration would flip the generation —
                # rebuilding every stack — for nothing, over and over under
                # an auto policy
                planned_rows = [0] * self.n_shards
                for seg in self.sealed:
                    planned_rows[plan[seg.uid]] += seg.n
                if (self._shard_skew(planned_rows)
                        >= self._shard_skew(rows_per_shard)):
                    _REBALANCE_DECLINES.inc()
                    return 0
                moves = [(seg, plan[seg.uid]) for seg in self.sealed
                         if plan[seg.uid] != seg.shard]
                if not moves:
                    _REBALANCE_DECLINES.inc()
                    return 0
                self._rebalance_active = True
            try:
                # device transfers OFF the lock: queries fan over the old
                # placement while the copies stream
                with obs.span("index.rebalance.transfer",
                              segments=len(moves)):
                    staged = [(seg, tgt, self._transfer_sketch(seg, tgt))
                              for seg, tgt in moves]
                with self._lock:
                    with obs.span("index.rebalance.commit") as csp:
                        live = {seg.uid for seg in self.sealed}
                        moved = 0
                        for seg, tgt, sk in staged:
                            if seg.uid not in live:
                                continue  # compacted away mid-transfer
                            seg.sketch = sk
                            seg._packed = None
                            seg._mask_dev = None
                            seg.shard = tgt
                            moved += 1
                        if moved:
                            self.generation += 1
                            self._segments_changed()
                            _REBALANCE_COMMITS.inc()
                            _REBALANCE_MOVED.inc(moved)
                        if csp:
                            csp.set(moved=moved, skipped=len(staged) - moved)
            finally:
                with self._lock:
                    self._rebalance_active = False
            if sp:
                sp.set(planned=len(moves), moved=moved)
            return moved

    def _transfer_sketch(self, seg: SealedSegment, shard: int) -> LpSketch:
        """Copy one sealed segment's sketch onto its target shard's device.

        Runs WITHOUT the index lock (sealed sketches are immutable; the
        ``_rebalance_active`` flag excludes the only other writer).  Blocks
        until the copy lands so the locked commit is a pure pointer flip."""
        dev = self.devices[shard % self.n_shards]
        sk = LpSketch(U=jax.device_put(seg.sketch.U, dev),
                      moments=jax.device_put(seg.sketch.moments, dev))
        jax.block_until_ready((sk.U, sk.moments))
        return sk

    def maybe_rebalance(self) -> int:
        """Consult the :class:`RebalancePolicy` and run one migration pass
        if it is due; returns segments moved (0 when the policy declines:
        no policy, skew below trigger, rate limited, or nothing to move)."""
        pol = self.rebalance_policy
        if pol is None:
            return 0
        now = pol.clock()
        with self._lock:
            if (self._last_rebalance_start is not None
                    and now - self._last_rebalance_start < pol.min_interval_s):
                return 0
        # the pass itself runs outside our lock hold: rebalance() stages its
        # device transfers lock-free and only flips placements under the
        # lock, so holding it here would serialize queries behind the copies
        moved = self.rebalance(skew_trigger=pol.skew_trigger)
        if moved:
            with self._lock:
                self.auto_rebalances += 1
        return moved

    def _arm_rebalance_limit(self) -> None:
        if self.rebalance_policy is not None:
            self._last_rebalance_start = self.rebalance_policy.clock()

    def _maybe_auto_compact(self) -> None:
        super()._maybe_auto_compact()
        if self.rebalance_policy is not None and self.rebalance_policy.auto:
            self.maybe_rebalance()

    def _swap_compacted(self, built) -> int:
        # a compaction swap is the moment delete skew becomes *height* skew
        # (segments shrink to their live rows) — self-heal right after it
        rewritten = super()._swap_compacted(built)
        if (rewritten and self.rebalance_policy is not None
                and self.rebalance_policy.auto):
            self.maybe_rebalance()
        return rewritten

    # ---------------------------------------------------------------- query

    def _plan(self, reduce: str, estimator: str,
              approx_ok: Optional[ApproxContract],
              deadline_ms: Optional[float] = None) -> QueryPlan:
        with self._lock:
            sealed = len(self.sealed)
        return self.planner.plan(
            reduce=reduce, estimator=estimator, sharded=True,
            mesh_available=self._fan_mesh is not None,
            sealed_segments=sealed, approx_ok=approx_ok,
            deadline_ms=deadline_ms, replica=self.replica_id)

    def _note_route(self, plan: QueryPlan, route: str, elapsed_s: float,
                    sp) -> None:
        """One served query: observed mode, legacy counters, cost sample."""
        label = STAGE1_LABEL[route]
        self._last_stage1 = label
        self._last_route[plan.estimator] = label
        (_STAGE1_PARALLEL if route == "stacked" else _STAGE1_DISPATCH).inc()
        self.planner.observe(plan, route, elapsed_s * 1e3)
        if sp:
            sp.set(stage1=label, planned=STAGE1_LABEL[plan.route])

    def _topk(self, qsk: LpSketch, top_k: int, estimator: str, approx_ok,
              deadline_ms, sp):
        registry.resolve(estimator, p=self.cfg.p,
                         projection=self.cfg.projection.family)
        _check_top_k(top_k)
        segments = self._segments()
        plan = self._plan("topk", estimator, approx_ok, deadline_ms)
        for route in plan.chain:
            t0 = time.perf_counter()
            out = self._run_topk_route(route, plan, qsk, segments, top_k)
            if out is not None:
                self._note_route(plan, route, time.perf_counter() - t0, sp)
                return out
        raise RuntimeError(  # dispatch is terminal: this cannot decline
            f"no route served the query (plan: {plan.describe()})")

    def _run_topk_route(self, route: str, plan: QueryPlan, qsk: LpSketch,
                        segments, top_k: int):
        """Execute one top-k route; None means this route declines (empty
        stack, failed approx gate) and the plan's next fallback runs."""
        if route == "stacked":
            spec = registry.get(plan.estimator)
            if spec.capabilities.stacked_topk == registry.STACKED_PACKED:
                return self._stacked_fan_topk(qsk, segments, top_k, spec)
            return self._stacked_fan_topk_mle(qsk, segments, top_k,
                                              plan.approx, spec)
        return sharded_fan_topk(qsk, segments, self.cfg, self.devices,
                                top_k=top_k, estimator=plan.estimator,
                                engine=self.engine)

    # ------------------------------------------------- parallel stage-1 fan

    def _stacked_operands(self, shard_groups, col_block: int
                          ) -> _StackedOperands:
        """Cached stacks for the current sealed snapshot.

        Keyed on each segment's process-monotonic ``uid`` plus its shard and
        stack offset: any seal / compaction swap / rebalance / reload changes
        the key.  ``id()`` must never be the key — after a swap drops old
        segments, CPython can hand their ids to the replacements, and the
        stale key would then serve stacks packed from freed segments."""
        key = (col_block,) + tuple(
            (s, b, seg.uid) for s, g in shard_groups for b, seg in g)
        st = self._stack
        if st is None or st.key != key:
            _STACK_MISSES.inc()
            st = _build_stacked_operands(
                shard_groups, self.n_shards, self._fan_mesh, self.devices,
                self.cfg, col_block, self.data_axes, key)
            self._stack = st
        else:
            _STACK_HITS.inc()
        return st

    def _stacked_mask(self, st: _StackedOperands):
        """(S, rows) device live mask, refreshed only when tombstones moved.

        A tombstone delta is applied *device-side*: each affected shard's
        resident (1, rows) mask block gets a scatter of just the flipped
        positions (``seg.tombstones_since``), so a delete costs O(deletes)
        per shard — never a (S, rows) host rebuild + ``device_put`` of the
        whole fleet's bitmap.  Falls back to the full host rebuild when the
        per-segment delta log has been trimmed (or on a fresh snapshot,
        where no mask exists yet)."""
        versions = tuple(
            seg.live_version for _s, g in st.groups for _b, seg in g)
        if st.mask is not None and st.mask_versions == versions:
            return st.mask
        if st.mask is not None:
            flips = self._mask_deltas(st)
            if flips is not None:
                if flips:
                    st.mask = self._scatter_mask(st.mask, flips)
                    st.mask_scatter_updates += 1
                    _MASK_SCATTERS.inc()
                st.mask_versions = versions
                return st.mask
        m = np.zeros((self.n_shards, st.rows), bool)
        for s, g in st.groups:
            m[s] = shard_stack_live(g, st.rows)
        st.mask = jax.device_put(
            m, NamedSharding(self._fan_mesh, P(self.data_axes, None)))
        st.mask_versions = versions
        st.mask_full_builds += 1
        _MASK_REBUILDS.inc()
        return st.mask

    def _mask_deltas(self, st: _StackedOperands):
        """{shard: stacked row indices tombstoned since the cached mask}, or
        None when some segment's delta is unreconstructible (log trimmed)."""
        flips: dict = {}
        it = iter(st.mask_versions)
        for s, g in st.groups:
            r0 = 0
            for _b, seg in g:
                cached = next(it)
                if seg.live_version != cached:
                    idx = seg.tombstones_since(cached)
                    if idx is None:
                        return None
                    if len(idx):
                        flips.setdefault(s, []).append(r0 + idx)
                r0 += seg.n
        return {s: np.concatenate(parts) for s, parts in flips.items()}

    def _scatter_mask(self, mask, flips):
        """Scatter False at ``flips[shard]`` into each shard's resident mask
        block on its own device, then restitch the global (S, rows) array —
        the mask never round-trips through the host."""
        parts = [None] * self.n_shards
        devs = [None] * self.n_shards
        for ash in mask.addressable_shards:
            s = ash.index[0].start or 0
            parts[s] = ash.data
            devs[s] = ash.device
        for s, idx in flips.items():
            parts[s] = jax.device_put(
                parts[s].at[0, idx].set(False), devs[s])
        return jax.make_array_from_single_device_arrays(
            (self.n_shards, mask.shape[1]), mask.sharding, parts)

    def _stacked_fan_topk(self, qsk: LpSketch, segments, top_k: int,
                          spec: registry.EstimatorSpec):
        """Stage 1 under ``shard_map``: all shards fold their stacked strips
        concurrently; stage 2 is the same host-side (value, position) re-rank
        as the dispatch fan, so results are bit-identical to it (and to the
        single-host index).  Returns None when nothing is sharded yet."""
        backend, _, col_block = (self.engine or EngineConfig()).resolve()
        groups, _ = _group_by_shard(segments, self.n_shards)
        shard_groups = [(s, g) for s, g in groups if s is not None]
        if not shard_groups:
            return None  # no sealed shards: the dispatch fan is the fan
        q = qsk.n
        n_live = sum(seg.live_count for seg in segments)
        k_out = min(top_k, n_live)
        if k_out == 0 or q == 0:
            # nothing to rank (or an empty batch): same shapes the
            # single-host fan early-returns — never dispatch a 0-row
            # shard_map program
            return (jnp.zeros((q, k_out), jnp.float32),
                    np.zeros((q, k_out), np.int64))

        with obs.span("index.fan.stage1", metric="index.stage1_parallel_ms",
                      mode="parallel", shards=len(shard_groups)):
            st = self._stacked_operands(shard_groups, col_block)
            q_packed = _pack_query(qsk, self.cfg, spec)
            Aq, nq = q_packed
            # one shard_map dispatch covers every shard's stage-1 fold ...
            # clamp the static top_k to the stack height: every k above it
            # compiles the identical program, so don't mint new cache entries
            vals_sh, pos_sh = stacked_topk_shards(
                Aq, nq, st.B, st.nb, self._stacked_mask(st), st.pos,
                mesh=self._fan_mesh, top_k=min(top_k, st.rows),
                col_block=col_block, backend=backend,
                data_axes=self.data_axes)
            # ... while the host-local group (active segment + any unplaced
            # sealed block) folds through the same per-segment strips as
            # always
            local_pending = [
                _shard_candidates(qsk, q_packed, grp, self.cfg, spec,
                                  backend, col_block, top_k, q)
                for s, grp in groups if s is None
            ]

            # only the (q, k) candidate lists leave the shards; the
            # device_get blocks, so the async shard_map compute lands here
            vals_np = np.asarray(jax.device_get(vals_sh))
            pos_np = np.asarray(jax.device_get(pos_sh))
            local_vals = [np.asarray(jax.device_get(v))
                          for v, _ in local_pending]
            local_pos = [np.asarray(jax.device_get(i))
                         for _, i in local_pending]
        with obs.span("index.fan.stage2"):
            cat_vals = np.concatenate(list(vals_np) + local_vals, axis=1)
            cat_pos = np.concatenate(list(pos_np) + local_pos, axis=1)
            k_out = _finite_k(cat_vals, k_out)
            vals, idx = rerank_topk(cat_vals, cat_pos, k_out)
            return vals, _ids_for_positions(segments, np.asarray(idx))

    def _stacked_mle_operands(self, st: _StackedOperands):
        """Per-shard raw-sketch stacks (U (S, R, nvec, k), moments
        (S, R, p-1)) for the approx mle fan, built lazily on the cached
        operand snapshot — same key, lifetime, positions, and live mask as
        the plain stacks."""
        if st.Usk is None:
            dax = self.data_axes
            group_of = dict(st.groups)
            parts_U, parts_M = [], []
            for s in range(self.n_shards):
                U_blk, M_blk = pack_shard_sketch_stack(
                    group_of.get(s, []), st.rows, self.cfg, self.devices[s])
                parts_U.append(U_blk[None])
                parts_M.append(M_blk[None])
            sh_U = NamedSharding(self._fan_mesh, P(dax, None, None, None))
            sh_M = NamedSharding(self._fan_mesh, P(dax, None, None))
            st.Usk = jax.make_array_from_single_device_arrays(
                (self.n_shards,) + parts_U[0].shape[1:], sh_U, parts_U)
            st.Msk = jax.make_array_from_single_device_arrays(
                (self.n_shards,) + parts_M[0].shape[1:], sh_M, parts_M)
        return st.Usk, st.Msk

    def _stacked_fan_topk_mle(self, qsk: LpSketch, segments, top_k: int,
                              contract: ApproxContract,
                              spec: registry.EstimatorSpec):
        """Margin-MLE stage 1 on the stacked ``shard_map`` fan — the
        ``approx_ok`` route.

        mle's Newton strips are not bitwise stable under the stacked
        re-tiling, so this route is tolerance-gated per operand snapshot:
        the first query against a given stack ALSO computes the exact
        dispatch answer and the snapshot is admitted only if every value
        agrees within the contract (``within_tolerance``), turning the
        measured ~2e-5 relative drift into an asserted bound.  A failed
        gate is memoized and this route declines (returns None), so the
        plan's dispatch fallback serves the stack from then on."""
        backend, _, col_block = (self.engine or EngineConfig()).resolve()
        groups, _ = _group_by_shard(segments, self.n_shards)
        shard_groups = [(s, g) for s, g in groups if s is not None]
        if not shard_groups:
            return None  # no sealed shards: the dispatch fan is the fan
        q = qsk.n
        n_live = sum(seg.live_count for seg in segments)
        k_out = min(top_k, n_live)
        if k_out == 0 or q == 0:
            return (jnp.zeros((q, k_out), jnp.float32),
                    np.zeros((q, k_out), np.int64))

        st = self._stacked_operands(shard_groups, col_block)
        gate_key = (f"{spec.name}_topk", st.key, contract)
        gate = self.planner.gate_status(gate_key)
        if gate is False:
            return None  # this snapshot failed the contract: dispatch serves

        with obs.span("index.fan.stage1", metric="index.stage1_parallel_ms",
                      mode="parallel", estimator=spec.name,
                      shards=len(shard_groups)):
            Usk, Msk = self._stacked_mle_operands(st)
            vals_sh, pos_sh = stacked_mle_topk_shards(
                qsk.U, qsk.moments, Usk, Msk, self._stacked_mask(st), st.pos,
                mesh=self._fan_mesh, cfg=self.cfg,
                top_k=min(top_k, st.rows), col_block=col_block,
                data_axes=self.data_axes)
            # the local group (active segment + unplaced sealed blocks)
            # folds through the exact per-segment mle strips as always
            local_pending = [
                _shard_candidates(qsk, None, grp, self.cfg, spec, backend,
                                  col_block, top_k, q)
                for s, grp in groups if s is None
            ]
            vals_np = np.asarray(jax.device_get(vals_sh))
            pos_np = np.asarray(jax.device_get(pos_sh))
            local_vals = [np.asarray(jax.device_get(v))
                          for v, _ in local_pending]
            local_pos = [np.asarray(jax.device_get(i))
                         for _, i in local_pending]
        with obs.span("index.fan.stage2"):
            cat_vals = np.concatenate(list(vals_np) + local_vals, axis=1)
            cat_pos = np.concatenate(list(pos_np) + local_pos, axis=1)
            k_out = _finite_k(cat_vals, k_out)
            vals, idx = rerank_topk(cat_vals, cat_pos, k_out)
            out = (vals, _ids_for_positions(segments, np.asarray(idx)))

        if gate is None:
            # calibrate ONCE per snapshot: the exact dispatch answer is the
            # reference the contract is asserted against.  Sorted rows are
            # 1-Lipschitz in the sup norm, so a per-value bound against the
            # sorted reference is sound even if near-ties reorder.
            ref_vals, _ref_ids = sharded_fan_topk(
                qsk, segments, self.cfg, self.devices, top_k=top_k,
                estimator=spec.name, engine=self.engine)
            ok, drift = within_tolerance(
                np.asarray(out[0]), np.asarray(ref_vals),
                rtol=contract.rtol, atol=contract.atol)
            self.planner.record_gate(gate_key, ok, drift)
            if not ok:
                return None  # fall back: dispatch recomputes (rare path)
        return out

    def _threshold(self, qsk: LpSketch, radius: float, relative: bool,
                   estimator: str, approx_ok, deadline_ms, sp):
        registry.resolve(estimator, p=self.cfg.p,
                         projection=self.cfg.projection.family)
        segments = self._segments()
        plan = self._plan("threshold", estimator, approx_ok, deadline_ms)
        for route in plan.chain:
            t0 = time.perf_counter()
            out = self._run_threshold_route(route, plan, qsk, segments,
                                            radius, relative)
            if out is not None:
                self._note_route(plan, route, time.perf_counter() - t0, sp)
                return out
        raise RuntimeError(
            f"no route served the query (plan: {plan.describe()})")

    def _run_threshold_route(self, route: str, plan: QueryPlan,
                             qsk: LpSketch, segments, radius: float,
                             relative: bool):
        if route == "stacked":
            # the planner only routes estimators whose spec declares
            # ``stacked_threshold`` here — packed-factor strips by
            # construction
            return self._stacked_threshold(qsk, segments, radius, relative,
                                           registry.get(plan.estimator))
        return sharded_threshold_scan(
            qsk, segments, self.cfg, self.devices, radius=radius,
            relative=relative, estimator=plan.estimator, engine=self.engine)

    def _stacked_threshold(self, qsk: LpSketch, segments, radius: float,
                           relative: bool, spec: registry.EstimatorSpec):
        """Threshold stage 1 under ``shard_map``: all shards evaluate the
        masked strict ``D < radius`` criterion over their stacked blocks
        concurrently (``core.distributed.stacked_threshold_shards``); only
        per-shard hit booleans leave the mesh, converted host-side to
        (query row, global position) pairs and merged with the local group's
        hits in the same (query, ingest-order) contract as the single-host
        ``threshold_scan`` — pair-for-pair identical.  The ``mle`` estimator
        never routes here (its Newton strips are not bitwise stable under
        XLA fusion), matching the top-k fan's rationale.  Returns None when
        nothing is sharded yet (the dispatch scan is the scan)."""
        backend, _, col_block = (self.engine or EngineConfig()).resolve()
        groups, _ = _group_by_shard(segments, self.n_shards)
        shard_groups = [(s, g) for s, g in groups if s is not None]
        if not shard_groups:
            return None
        if qsk.n == 0:
            # empty batch: the merge of zero hits, same as the single-host
            # scan — never dispatch a 0-row shard_map program
            return _merge_threshold_hits([], [])
        with obs.span("index.fan.stage1", metric="index.stage1_parallel_ms",
                      mode="parallel", shards=len(shard_groups)):
            st = self._stacked_operands(shard_groups, col_block)
            q_packed = _pack_query(qsk, self.cfg, spec)
            Aq, nq = q_packed
            hits_sh = stacked_threshold_shards(
                Aq, nq, st.B, st.nb, self._stacked_mask(st),
                jnp.float32(radius), mesh=self._fan_mesh, relative=relative,
                col_block=col_block, backend=backend,
                data_axes=self.data_axes)
            # local (active / unplaced) segments run the exact single-host
            # strip loop concurrently with the device fan
            nq_h = np.asarray(qsk.norm_pp(self.cfg.p))
            rows_out, ids_out = [], []
            for s, grp in groups:
                if s is not None:
                    continue
                for _base, seg in grp:
                    rr, ii = _segment_threshold_hits(
                        qsk, q_packed, seg, self.cfg, spec, backend,
                        col_block, nq_h, radius, relative)
                    rows_out.extend(rr)
                    ids_out.extend(ii)
            # only the per-shard hit booleans cross the shard boundary
            hits_np = np.asarray(jax.device_get(hits_sh))
        with obs.span("index.fan.stage2"):
            for s, _g in shard_groups:
                rr, cc = np.nonzero(hits_np[s])
                if len(rr):
                    pos = st.pos_host[s][cc]
                    rows_out.append(rr)
                    ids_out.append(_ids_for_positions(segments, pos))
            return _merge_threshold_hits(rows_out, ids_out)

    # ----------------------------------------------------------- persistence

    @classmethod
    def load(cls, path: str, *, engine: Optional[EngineConfig] = None,
             mesh=None, devices: Optional[Sequence] = None,
             data_axes="data", policy: Optional[CompactionPolicy] = None,
             rebalance_policy: Optional[RebalancePolicy] = None
             ) -> "ShardedSketchIndex":
        """Restore with sharding hints: each stored segment is ``device_put``
        onto its shard as it loads (multi-host restore path)."""
        from .store import load_index
        if mesh is None and devices is None:
            devices = jax.devices()
        index = load_index(path, engine=engine, mesh=mesh, devices=devices,
                           data_axes=data_axes, policy=policy,
                           rebalance_policy=rebalance_policy)
        assert isinstance(index, cls)
        return index
