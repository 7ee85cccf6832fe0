"""Replica fan-out: serve one corpus from R independent serving lanes.

First use of the serving mesh's ``replica`` axis
(``make_serving_mesh(n_shards, n_replicas=R)`` — an R x N device grid).
Lane 0 *is* the primary index (writes always land there); lanes 1..R-1 hold
replica views of the primary's sealed segments whose sketches were
``jax.device_put`` onto that replica's mesh row.  ``device_put`` moves bits
and never recomputes them, and the replica fan runs the same per-segment
dispatch strip programs as the primary — so every lane's answer is
**bit-identical** to the replica=1 path (pinned by the lifecycle test).

Queries go to exactly ONE lane (there is no cross-replica collective):
:meth:`ReplicaSet.query` routes around a busy replica with the same
least-loaded + EWMA-with-hysteresis discipline as the planner's cost model
— lowest in-flight wins, and among equally-loaded lanes a measured EWMA
only displaces the preferred lane when it is decisively
(``hysteresis``-times) cheaper, so routing never flaps on noise.

Consistency model: deletes propagate immediately (views share the primary's
``live`` bitmaps — tombstones are host-side bitmap flips); structural
changes (seal/compact/ingest) propagate on the next query via a
``generation`` check, the same snapshot semantics the primary's own queries
have.

Example::

    >>> import numpy as np
    >>> from repro.core.sketch import SketchConfig
    >>> from repro.index import SketchIndex
    >>> from repro.serve import ReplicaSet
    >>> idx = SketchIndex(SketchConfig(p=4, k=16, block_d=32))
    >>> _ = idx.ingest(np.ones((8, 32), np.float32))
    >>> rs = ReplicaSet(idx, n_replicas=2)
    >>> d, ids = rs.query(np.ones((1, 32), np.float32), top_k=3)
    >>> ids.shape
    (1, 3)
"""

from __future__ import annotations

import threading
import time
from typing import List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core import registry
from repro.core.pairwise import pack_right
from repro.core.sketch import sketch
from repro.index.sharded import sharded_fan_topk, sharded_threshold_scan
from repro.obs.metrics import REGISTRY

__all__ = ["ReplicaSet"]

_REPLICA_SYNCS = REGISTRY.counter(
    "scheduler.replica_syncs", "replica lane segment-view rebuilds")


class _ReplicaSegment:
    """A replica-local, read-only view of a primary sealed segment.

    Owns its own sketch reference (``device_put`` onto the replica's device
    when one is given — bits moved, never recomputed) and its own lazy
    packed/mask caches (device-resident state must live on the replica's
    devices), while *sharing* the primary's ``live`` bitmap so tombstones
    propagate without a sync.  The device mask cache re-validates against
    the source's ``live_version`` — a delete on the primary invalidates
    every replica's mask on its next read.
    """

    def __init__(self, src, device=None):
        self._src = src
        self.sketch = (src.sketch if device is None
                       else jax.device_put(src.sketch, device))
        self.row_ids = src.row_ids
        self.shard: Optional[int] = None  # set by the lane's round-robin
        self._packed = None
        self._mask_dev = None
        self._mask_version = -1

    @property
    def n(self) -> int:
        return self._src.n

    @property
    def live(self) -> np.ndarray:
        return self._src.live

    @property
    def live_count(self) -> int:
        return self._src.live_count

    def packed(self, cfg):
        """(B, nb) right factors, built lazily from the replica-local sketch
        — same deterministic ``pack_right`` program as seal time, so the
        factors match the primary's bit for bit."""
        if self._packed is None:
            self._packed = pack_right(self.sketch, cfg)
        return self._packed

    def mask(self) -> jax.Array:
        if self._mask_dev is None or self._mask_version != self._src.live_version:
            self._mask_version = self._src.live_version
            self._mask_dev = jnp.asarray(self._src.live)
        return self._mask_dev


class _Lane:
    """One serving lane: a synced view list + routing state."""

    def __init__(self, replica_id: int, devices):
        self.replica_id = replica_id
        self.devices = list(devices) if devices is not None else None
        self.segments: Optional[list] = None  # sealed views; None = unsynced
        # (generation, sealed count, active identity): generation only moves
        # on compaction flips, so seals — which append to the sealed list
        # and swap in a fresh ActiveSegment — are caught by the other two
        self.sync_key = None
        self.served = 0
        self.inflight = 0
        self.ewma_ms: Optional[float] = None
        self.samples = 0


class ReplicaSet:
    """Route queries over replica lanes of one writable primary index.

    Duck-types the index query surface the :class:`repro.index.MicroBatcher`
    expects (``query``/``query_threshold``/``n_live``/``stats``), so the
    front door simply wraps a ``ReplicaSet`` in its batcher.  Writes
    (ingest/delete/seal/compact) go to ``primary`` directly — this class
    only reads.

    ``replica_devices`` — optional ``[per-replica device list, ...]`` (one
    entry per lane, e.g. from ``core.distributed.mesh_replica_devices`` over
    an R x N serving mesh).  Without it every lane serves from the default
    device, which still exercises the full view/sync machinery (the CI
    configuration).
    """

    hysteresis = 1.5   # a lane displaces the preferred one only decisively
    min_samples = 3    # ... and only once its EWMA is real
    alpha = 0.25

    def __init__(self, primary, *, n_replicas: int = 1,
                 replica_devices: Optional[Sequence] = None):
        if n_replicas < 1:
            raise ValueError(f"n_replicas must be >= 1, got {n_replicas}")
        if replica_devices is not None and len(replica_devices) != n_replicas:
            raise ValueError(
                f"replica_devices has {len(replica_devices)} entries for "
                f"{n_replicas} replicas")
        self.primary = primary
        if n_replicas > 1:
            primary.replica_id = 0  # plans served by lane 0 say so
        self.lanes: List[_Lane] = [
            _Lane(r, replica_devices[r] if replica_devices is not None
                  else None)
            for r in range(n_replicas)
        ]
        self._lock = threading.Lock()
        self.syncs = 0

    # ------------------------------------------------------------- routing

    @property
    def n_replicas(self) -> int:
        return len(self.lanes)

    @property
    def n_live(self) -> int:
        return self.primary.n_live

    def _measured(self, lane: _Lane) -> Optional[float]:
        return lane.ewma_ms if lane.samples >= self.min_samples else None

    def _pick(self, replica: Optional[int] = None) -> int:
        """Least-loaded lane; among equally-loaded lanes the lowest index
        stands unless a later lane's measured EWMA is decisively cheaper
        (the planner's hysteresis discipline — route around a busy or
        persistently slow replica, never flap).  ``replica`` pins the lane
        explicitly (draining/debugging; the tests use it to prove every
        lane answers bit-identically)."""
        with self._lock:
            if replica is not None:
                if not 0 <= replica < len(self.lanes):
                    raise ValueError(
                        f"replica must be in [0, {len(self.lanes)}), "
                        f"got {replica}")
                best = self.lanes[replica]
            else:
                best = self.lanes[0]
                for lane in self.lanes[1:]:
                    if lane.inflight < best.inflight:
                        best = lane
                    elif lane.inflight == best.inflight:
                        cb, cl = self._measured(best), self._measured(lane)
                        if (cb is not None and cl is not None
                                and cb > self.hysteresis * cl):
                            best = lane
            best.inflight += 1
            return best.replica_id

    def _observe(self, lane: _Lane, elapsed_ms: float) -> None:
        with self._lock:
            lane.inflight = max(0, lane.inflight - 1)
            lane.served += 1
            lane.samples += 1
            lane.ewma_ms = (elapsed_ms if lane.ewma_ms is None else
                            (1.0 - self.alpha) * lane.ewma_ms
                            + self.alpha * elapsed_ms)

    def _abandon(self, lane: _Lane) -> None:
        with self._lock:
            lane.inflight = max(0, lane.inflight - 1)

    # ---------------------------------------------------------------- sync

    def _synced_segments(self, lane: _Lane) -> list:
        """Lane's segment list for one query: sealed views (rebuilt when the
        primary's structure moved — compaction flip, seal, load) plus the
        primary's live active segment.  Snapshot under the primary's lock —
        the same consistency the primary's own queries get.  The active
        segment is host-append-only, so every lane reads the primary's
        object directly (local group of the fan) and new ingests are
        visible without a sync; deletes propagate through the shared
        ``live`` bitmaps the same way."""
        prim = self.primary
        with prim._lock:
            key = (prim.generation, len(prim.sealed), id(prim.active))
            stale = lane.segments is None or lane.sync_key != key
            sealed = list(prim.sealed) if stale else None
            active = prim.active if prim.active.size else None
        if stale:
            n_dev = len(lane.devices) if lane.devices else 1
            views: list = []
            for i, seg in enumerate(sealed):
                dev = lane.devices[i % n_dev] if lane.devices else None
                view = _ReplicaSegment(seg, dev)
                view.shard = (i % n_dev) if lane.devices else None
                views.append(view)
            with self._lock:
                lane.segments = views
                lane.sync_key = key
                self.syncs += 1
            _REPLICA_SYNCS.inc()
        segs = list(lane.segments)
        if active is not None:
            segs.append(active)
        return segs

    # --------------------------------------------------------------- query

    def query(self, rows, top_k: int = 10,
              estimator: str = registry.DEFAULT_ESTIMATOR, *,
              approx_ok=None, deadline_ms: Optional[float] = None,
              replica: Optional[int] = None):
        """Top-k via one replica lane — results bit-identical to
        ``primary.query`` regardless of which lane serves.  ``approx_ok``
        and ``deadline_ms`` are forwarded to the primary's planner on lane
        0; replica lanes run the exact dispatch fan, which accepts and
        ignores both (same contract as the single-host fan).  ``replica``
        pins the lane (None = route)."""
        r = self._pick(replica)
        lane = self.lanes[r]
        t0 = time.perf_counter()
        try:
            if r == 0:
                out = self.primary.query(rows, top_k=top_k,
                                         estimator=estimator,
                                         approx_ok=approx_ok,
                                         deadline_ms=deadline_ms)
            else:
                with obs.span("serve.replica", replica=r, kind="topk"):
                    prim = self.primary
                    segs = self._synced_segments(lane)
                    qsk = sketch(jnp.asarray(np.atleast_2d(rows)), prim.key,
                                 prim.cfg)
                    out = sharded_fan_topk(
                        qsk, segs, prim.cfg,
                        lane.devices if lane.devices else [None],
                        top_k=top_k, estimator=estimator, engine=prim.engine)
        except BaseException:
            self._abandon(lane)
            raise
        self._observe(lane, (time.perf_counter() - t0) * 1e3)
        return out

    def query_threshold(self, rows, radius: float, *, relative: bool = False,
                        estimator: str = registry.DEFAULT_ESTIMATOR,
                        approx_ok=None,
                        deadline_ms: Optional[float] = None,
                        replica: Optional[int] = None):
        """(query_rows, row_ids) with D < radius via one replica lane —
        pair-for-pair identical to ``primary.query_threshold``."""
        r = self._pick(replica)
        lane = self.lanes[r]
        t0 = time.perf_counter()
        try:
            if r == 0:
                out = self.primary.query_threshold(
                    rows, radius, relative=relative, estimator=estimator,
                    approx_ok=approx_ok, deadline_ms=deadline_ms)
            else:
                with obs.span("serve.replica", replica=r, kind="threshold"):
                    prim = self.primary
                    segs = self._synced_segments(lane)
                    qsk = sketch(jnp.asarray(np.atleast_2d(rows)), prim.key,
                                 prim.cfg)
                    out = sharded_threshold_scan(
                        qsk, segs, prim.cfg,
                        lane.devices if lane.devices else [None],
                        radius=radius, relative=relative,
                        estimator=estimator, engine=prim.engine)
        except BaseException:
            self._abandon(lane)
            raise
        self._observe(lane, (time.perf_counter() - t0) * 1e3)
        return out

    # -------------------------------------------------------------- readout

    def stats(self) -> dict:
        with self._lock:
            lanes = [
                {"replica": lane.replica_id,
                 "served": lane.served,
                 "inflight": lane.inflight,
                 "ewma_ms": (None if lane.ewma_ms is None
                             else round(lane.ewma_ms, 4)),
                 "synced": lane.segments is not None,
                 "devices": (None if lane.devices is None
                             else len(lane.devices))}
                for lane in self.lanes
            ]
            syncs = self.syncs
        return {"n_replicas": len(lanes), "syncs": syncs, "lanes": lanes}
