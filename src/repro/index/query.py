"""Query planning: fan the engine's fused reductions across segments.

``fan_topk`` streams each segment through the engine's strip machinery
(packed-matmul strips when the resolved estimator spec declares
``uses_packed``, the spec's own strip function otherwise) with tombstones
masked to ``+inf`` *after* the strip estimate (``where`` keeps live-row values
bit-identical), then folds the per-segment candidate lists with the engine's
``merge_topk``.  Tie-breaking matches a dense ``knn`` over the equivalent
live corpus exactly: within a segment the engine resolves ties to the lowest
local column; across segments the running candidate list always precedes the
newer segment's candidates in the merge concatenation, and segments are
visited in creation (= ingest) order — so equal distances resolve to the
earliest-ingested live row, same as dense.

``threshold_scan`` routes the same masked strips through the engine's
threshold criterion, yielding (query_row, row_id) pairs.

``MicroBatcher`` is the serving front door: concurrent callers' query rows
are coalesced into one fused engine pass per (top_k, estimator, approx_ok)
group — one sketch call + one fan per batch instead of one per request.
"""

from __future__ import annotations

import threading
from typing import List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core import registry
from repro.core.pairwise import pack_sketch
from repro.core.registry import EstimatorSpec
from repro.core.sketch import LpSketch, SketchConfig
from repro.engine import EngineConfig, strip_distances
from repro.engine.reduce import merge_topk, strip_bounds
from repro.obs.metrics import REGISTRY

from .segment import ActiveSegment, SealedSegment

__all__ = ["fan_topk", "threshold_scan", "MicroBatcher"]

# fleet-wide batcher counters (always live — they ARE the serving stats);
# resolved once at import so the flush path never takes the registry lock
_BATCHES_TOTAL = REGISTRY.counter(
    "batcher.batches", "micro-batches flushed, all batchers")
_ROWS_TOTAL = REGISTRY.counter(
    "batcher.rows", "query rows served through micro-batches")
# batch-size buckets are row counts, not latencies
_BATCH_ROWS_BUCKETS = (1.0, 2.0, 4.0, 8.0, 16.0, 32.0, 64.0, 128.0, 256.0,
                       512.0, 1024.0)
_DEADLINE_FLUSHES = REGISTRY.counter(
    "batcher.deadline_flushes",
    "partial batches shipped early because a waiter's deadline budget was "
    "at risk")
# when the flush-latency histogram is empty (tracing never ran), assume a
# flush costs this much when deciding how long a deadline holder may wait —
# conservative enough to leave budget for the engine pass itself
_DEFAULT_FLUSH_BUDGET_MS = 1.0

_IDX_SENTINEL = np.iinfo(np.int32).max

Segment = Union[ActiveSegment, SealedSegment]


def _check_top_k(top_k) -> None:
    """Friendly contract errors instead of shape crashes deep in the fan.

    ``top_k`` larger than the live-row count is fine — every fan returns
    min(top_k, live) columns, even off shards holding only padded stacked
    blocks — but a negative or non-integer k would otherwise surface as an
    inscrutable reshape/top_k shape error strips deep."""
    if isinstance(top_k, bool) or not isinstance(top_k, (int, np.integer)):
        raise ValueError(
            f"top_k must be an integer, got {type(top_k).__name__} {top_k!r}")
    if top_k < 0:
        raise ValueError(
            f"top_k must be >= 0, got {top_k} (results always have "
            "min(top_k, live rows) columns; ask for 0 to get none)")


def _finite_k(vals_np: np.ndarray, k_out: int) -> int:
    """Shrink k_out to the finite candidates every query row actually has.

    ``k_out = min(top_k, n_live)`` is computed from a live-count snapshot; a
    delete racing the fan can tombstone rows after that snapshot, leaving
    fewer finite candidates than promised.  Masked (dead/padded) candidates
    carry ``+inf``, so clamping to the per-row finite count returns a
    narrower (still consistent) answer instead of surfacing dead rows or
    sentinel positions.  ``vals_np`` is the full candidate array, sorted or
    not — finite entries are counted, never assumed to be a prefix."""
    if vals_np.shape[0] == 0 or k_out == 0:
        return k_out
    return min(k_out, int(np.isfinite(vals_np).sum(axis=1).min()))


def _pack_query(qsk: LpSketch, cfg: SketchConfig, spec: EstimatorSpec):
    """Query-side factors, computed once per fan (segment-invariant)."""
    if not spec.uses_packed:
        return None
    Aq, _, nq = pack_sketch(qsk, cfg)
    return Aq, nq


def _segment_strip_fn(qsk: LpSketch, q_packed, seg: Segment,
                      cfg: SketchConfig, spec: EstimatorSpec, backend: str):
    """strip(c0, c1) -> (q, c1-c0) masked distance strip for one segment."""
    mask = seg.mask()
    if spec.uses_packed:
        if isinstance(seg, ActiveSegment):
            _, B, nb = pack_sketch(seg.as_sketch(), cfg)
        else:
            B, nb = seg.packed(cfg)
        Aq, nq = q_packed

        def strip(c0: int, c1: int) -> jax.Array:
            D = strip_distances(Aq, B[c0:c1], nq, nb[c0:c1],
                                backend=backend, clip=True)
            return jnp.where(mask[c0:c1][None, :], D, jnp.inf)
    else:
        seg_sk = seg.as_sketch() if isinstance(seg, ActiveSegment) else seg.sketch

        def strip(c0: int, c1: int) -> jax.Array:
            D = spec.pairwise(
                qsk,
                LpSketch(U=seg_sk.U[c0:c1], moments=seg_sk.moments[c0:c1]),
                cfg, clip=True,
            )
            return jnp.where(mask[c0:c1][None, :], D, jnp.inf)

    return strip


def _segment_rows(seg: Segment) -> int:
    return seg.capacity if isinstance(seg, ActiveSegment) else seg.n


def _fold_segment_topk(vals, idx, qsk, q_packed, seg: Segment,
                       cfg: SketchConfig, spec: EstimatorSpec, backend: str,
                       col_block: int, base: int, k: int):
    """Fold one segment's strips into a running (q, k) candidate list, with
    columns globalized at ``base``.  The single-host fan and the sharded
    stage-1 fans both run THIS loop, so their per-segment candidates are
    identical by construction."""
    n = _segment_rows(seg)
    strip = _segment_strip_fn(qsk, q_packed, seg, cfg, spec, backend)
    c = min(k, n)
    for c0, c1 in strip_bounds(n, col_block):
        D = strip(c0, c1)
        neg, j = jax.lax.top_k(-D, min(c, c1 - c0))
        cand_idx = (j + (base + c0)).astype(jnp.int32)
        vals, idx = merge_topk(vals, idx, -neg, cand_idx, k)
    return vals, idx


def _segment_threshold_hits(qsk, q_packed, seg: Segment, cfg: SketchConfig,
                            spec: EstimatorSpec, backend: str, col_block: int,
                            nq_h: np.ndarray, radius: float, relative: bool):
    """One segment's (query_rows, row_ids) hit pairs, unsorted.  Shared by
    the single-host and sharded threshold scans — one copy of the radius
    criterion and the masking contract."""
    n = _segment_rows(seg)
    seg_sk = seg.as_sketch() if isinstance(seg, ActiveSegment) else seg.sketch
    nb_h = np.asarray(seg_sk.norm_pp(cfg.p))
    strip = _segment_strip_fn(qsk, q_packed, seg, cfg, spec, backend)
    ids = seg.row_ids
    rows_out, ids_out = [], []
    # the radius comparison is a float32 contract: strips are float32, and the
    # device-side scans (stacked fan, pairwise_sharded) compare in float32 —
    # a float64 host comparison would flip ties exactly at the radius
    r32 = np.float32(radius)
    for c0, c1 in strip_bounds(n, col_block):
        D = np.asarray(strip(c0, c1))
        if relative:
            scale = nq_h[:, None] + nb_h[None, c0:c1]
            hit = D < r32 * scale
        else:
            hit = D < r32
        rr, cc = np.nonzero(hit)
        rows_out.append(rr)
        ids_out.append(ids[cc + c0])
    return rows_out, ids_out


def _merge_threshold_hits(rows_out, ids_out):
    """Fold collected per-segment hits into (query, ingest-order) order —
    the engine's row-major dense contract (ids are monotone in ingest
    position, so the id sort IS the position sort)."""
    if not rows_out:
        return np.zeros(0, np.intp), np.zeros(0, np.int64)
    rows, hit_ids = np.concatenate(rows_out), np.concatenate(ids_out)
    order = np.lexsort((hit_ids, rows))
    return rows[order], hit_ids[order]


def fan_topk(
    qsk: LpSketch,
    segments: Sequence[Segment],
    cfg: SketchConfig,
    *,
    top_k: int,
    estimator: str = registry.DEFAULT_ESTIMATOR,
    engine: Optional[EngineConfig] = None,
) -> Tuple[jax.Array, np.ndarray]:
    """(distances (q, k), row_ids (q, k)) over all live rows, ascending,
    k = min(top_k, total live rows).  Dead/padded rows never surface."""
    spec = registry.resolve(estimator, p=cfg.p,
                            projection=cfg.projection.family)
    _check_top_k(top_k)
    backend, _, col_block = (engine or EngineConfig()).resolve()
    q = qsk.n
    n_live = sum(seg.live_count for seg in segments)
    k_out = min(top_k, n_live)
    if k_out == 0:
        return (jnp.zeros((q, 0), jnp.float32), np.zeros((q, 0), np.int64))

    # merge in global-position space (segment base + local column): position
    # order == ingest order, which is the dense corpus's tie-break order
    total = sum(_segment_rows(s) for s in segments)
    k_run = min(top_k, total)
    base = 0
    id_map: List[np.ndarray] = []
    with obs.span("index.fan.stage1", metric="index.stage1_dense_ms",
                  mode="single", segments=len(segments)) as sp:
        # jax dispatch is async: the dispatch span times the host's strip
        # loop, and the device work it queued is waited for under collect
        with obs.span("index.fan.dispatch"):
            vals = jnp.full((q, k_run), jnp.inf, jnp.float32)
            idx = jnp.full((q, k_run), _IDX_SENTINEL, jnp.int32)
            q_packed = _pack_query(qsk, cfg, spec)
            strips = 0
            for seg in segments:
                n = _segment_rows(seg)
                vals, idx = _fold_segment_topk(vals, idx, qsk, q_packed, seg,
                                               cfg, spec, backend, col_block,
                                               base, k_run)
                id_map.append(seg.row_ids[:n])
                base += n
                if sp:
                    strips += len(strip_bounds(n, col_block))
        if sp:
            sp.set(strips=strips)
        with obs.span("index.fan.collect"):
            pos_to_id = (np.concatenate(id_map) if id_map
                         else np.zeros(0, np.int64))
            k_out = _finite_k(np.asarray(vals), k_out)
            pos = np.asarray(idx[:, :k_out])
    return vals[:, :k_out], pos_to_id[pos]


def threshold_scan(
    qsk: LpSketch,
    segments: Sequence[Segment],
    cfg: SketchConfig,
    *,
    radius: float,
    relative: bool = False,
    estimator: str = registry.DEFAULT_ESTIMATOR,
    engine: Optional[EngineConfig] = None,
) -> Tuple[np.ndarray, np.ndarray]:
    """(query_rows, row_ids) of live pairs with D < radius (optionally
    relative to the marginal-norm scale), in (query, ingest-order) order."""
    spec = registry.resolve(estimator, p=cfg.p,
                            projection=cfg.projection.family)
    backend, _, col_block = (engine or EngineConfig()).resolve()
    nq_h = np.asarray(qsk.norm_pp(cfg.p))
    rows_out, ids_out = [], []
    q_packed = _pack_query(qsk, cfg, spec)
    for seg in segments:
        rr, ii = _segment_threshold_hits(qsk, q_packed, seg, cfg, spec,
                                         backend, col_block, nq_h, radius,
                                         relative)
        rows_out.extend(rr)
        ids_out.extend(ii)
    return _merge_threshold_hits(rows_out, ids_out)


class MicroBatcher:
    """Coalesce concurrent single/few-row queries into one fused index pass.

    Callers block in ``query``; a request joins the open batch for its
    (top_k, estimator, approx_ok) group and is flushed when the batch
    reaches ``max_batch`` rows or ``max_wait_ms`` elapses (whichever first).
    One sketch + one segment fan serves the whole batch.

    Deadline-aware closing: a caller may pass ``deadline_ms`` (its remaining
    latency budget).  The batch then tracks the *tightest* absolute deadline
    among its waiters, and every waiter shortens its wait so the flush
    starts while that budget — minus the observed p99 flush cost from the
    ``batcher.flush_ms`` histogram — is still intact.  A partial batch ships
    early rather than blowing the oldest waiter's deadline; the batcher
    itself never rejects (admission control and typed shedding live in
    ``repro.serve.FrontDoor``).

    Example::

        >>> from repro.index import MicroBatcher, SketchIndex
        >>> from repro.core.sketch import SketchConfig
        >>> import numpy as np
        >>> idx = SketchIndex(SketchConfig(p=4, k=16, block_d=32))
        >>> _ = idx.ingest(np.ones((8, 32), np.float32))
        >>> mb = MicroBatcher(idx, max_wait_ms=1.0)
        >>> dists, ids = mb.query(np.ones((1, 32), np.float32), top_k=3,
        ...                       deadline_ms=50.0)
        >>> ids.shape
        (1, 3)
    """

    def __init__(self, index, *, max_batch: int = 64, max_wait_ms: float = 2.0):
        self.index = index
        self.max_batch = max_batch
        self.max_wait = max_wait_ms / 1e3
        self._lock = threading.Lock()
        self._groups: dict = {}  # (top_k, estimator, approx_ok) -> _Batch
        # atomic instruments, NOT bare ints: the flush path runs on whichever
        # caller claims the batch, so two flushes can finish concurrently and
        # a read-modify-write outside the batch lock would drop counts
        self._batches = obs.Counter("batches_run")
        self._rows = obs.Counter("rows_served")
        self._deadline_flushes = obs.Counter("deadline_flushes")

    @property
    def batches_run(self) -> int:
        return self._batches.value

    @property
    def rows_served(self) -> int:
        return self._rows.value

    @property
    def deadline_flushes(self) -> int:
        return self._deadline_flushes.value

    def flush_budget_ms(self) -> float:
        """How long a flush is expected to take: observed p99 of
        ``batcher.flush_ms`` (filled while tracing is enabled), with a
        conservative default before any flush has been measured.  The
        deadline closer subtracts this from a waiter's remaining budget."""
        hist = REGISTRY.get("batcher.flush_ms")
        if hist is not None and getattr(hist, "count", 0) > 0:
            return float(hist.percentile(99))
        return _DEFAULT_FLUSH_BUDGET_MS

    def _wait_budget(self, deadline_abs: Optional[float],
                     now: Optional[float] = None) -> float:
        """Seconds this waiter may sleep before claiming a flush: the default
        ``max_wait``, shortened so a batch holding a deadline flushes while
        ``deadline - p99 flush cost`` budget remains.  <= 0 means flush NOW
        (the budget is already at risk).  Pure given (deadline_abs, now) —
        the deterministic-clock tests drive it directly."""
        if deadline_abs is None:
            return self.max_wait
        if now is None:
            now = obs.trace.clock()
        budget = (deadline_abs - now) - self.flush_budget_ms() / 1e3
        return min(self.max_wait, budget)

    def stats(self) -> dict:
        """Serving counters, live queue state, and (when tracing has run)
        latency/shape summaries from the process-global registry.

        ``queue_depth`` is the number of rows currently waiting in open
        batches and ``oldest_wait_ms`` how long the oldest open batch has
        been waiting — the two live signals the overload playbook (and the
        front door's queue gauges) read; completed-flush histograms alone
        cannot show a stuck or saturated queue."""
        now = obs.trace.clock()
        with self._lock:
            open_groups = len(self._groups)
            queue_depth = sum(b.n for b in self._groups.values())
            oldest = min((b.t_open for b in self._groups.values()),
                         default=None)
        return {
            "batches_run": self.batches_run,
            "rows_served": self.rows_served,
            "deadline_flushes": self.deadline_flushes,
            "open_groups": open_groups,
            "queue_depth": queue_depth,
            "oldest_wait_ms": (0.0 if oldest is None
                               else max(0.0, (now - oldest) * 1e3)),
            "queue_wait_ms": REGISTRY.histogram(
                "batcher.queue_wait_ms").summary(),
            "batch_rows": REGISTRY.histogram(
                "batcher.batch_rows", buckets=_BATCH_ROWS_BUCKETS).summary(),
            "flush_ms": REGISTRY.histogram("batcher.flush_ms").summary(),
        }

    class _Batch:
        def __init__(self):
            self.rows: List[np.ndarray] = []
            self.n = 0
            self.done = threading.Event()
            self.results = None
            self.error: Optional[BaseException] = None
            self.t_open = obs.trace.clock()  # for the queue-wait histogram
            self.joined: List[float] = []  # each request's join time
            self.deadline: Optional[float] = None  # tightest absolute deadline

    def query(self, rows, top_k: int = 10,
              estimator: str = registry.DEFAULT_ESTIMATOR,
              approx_ok=None, *, deadline_ms: Optional[float] = None):
        """(distances (b, k), row_ids (b, k)) for this caller's rows, with
        k = min(top_k, index live rows).  Validated up front: a malformed
        ``top_k`` fails only this caller, never the coalesced batch it would
        otherwise poison.  ``approx_ok`` is part of the batch key: callers
        holding different tolerance contracts never share a fused pass (the
        contract decides the route, and the route decides the answer).
        ``deadline_ms`` (remaining budget, not part of the key) arms the
        deadline-aware closer: the batch's tightest deadline governs when a
        partial batch ships early."""
        _check_top_k(top_k)
        rows = np.atleast_2d(np.asarray(rows))
        if rows.shape[0] == 0:
            # empty request: answer immediately — joining a batch would push
            # a degenerate 0-row strip through the engine fan
            k_out = min(top_k, self.index.n_live)
            return (jnp.zeros((0, k_out), jnp.float32),
                    np.zeros((0, k_out), np.int64))
        now = obs.trace.clock()
        deadline_abs = None if deadline_ms is None else now + deadline_ms / 1e3
        key = (top_k, estimator, approx_ok)
        with self._lock:
            batch = self._groups.get(key)
            if batch is None:
                batch = self._groups[key] = self._Batch()
            my = batch
            lo = my.n
            my.rows.append(rows)
            my.joined.append(now)
            my.n += rows.shape[0]
            if deadline_abs is not None and (my.deadline is None
                                             or deadline_abs < my.deadline):
                my.deadline = deadline_abs
            full = my.n >= self.max_batch
            if full:
                self._groups.pop(key, None)
        if full:
            self._run(my, key)
        else:
            wait = self._wait_budget(my.deadline)
            if wait > 0 and my.done.wait(wait):
                pass  # someone else flushed while we slept
            else:
                with self._lock:
                    # whoever times out first claims the flush
                    claimed = self._groups.get(key) is my
                    if claimed:
                        self._groups.pop(key, None)
                if claimed:
                    if my.deadline is not None and wait < self.max_wait:
                        # shipped early: the deadline, not the batch window,
                        # closed this batch
                        self._deadline_flushes.inc()
                        _DEADLINE_FLUSHES.inc()
                    self._run(my, key)
                my.done.wait()
        if my.error is not None:
            raise my.error
        dists, ids = my.results
        return dists[lo:lo + rows.shape[0]], ids[lo:lo + rows.shape[0]]

    def _run(self, batch: "_Batch", key) -> None:
        top_k, estimator, approx_ok = key
        try:
            X = np.concatenate(batch.rows, axis=0)
            n = X.shape[0]
            if obs.enabled():
                REGISTRY.histogram(
                    "batcher.queue_wait_ms",
                    "ms a batch waited open before its flush started",
                ).observe((obs.trace.clock() - batch.t_open) * 1e3)
                REGISTRY.histogram(
                    "batcher.batch_rows", "rows coalesced per flushed batch",
                    buckets=_BATCH_ROWS_BUCKETS).observe(n)
            # the flusher's trace carries the whole coalesced batch — the
            # engine ran once, so that is the honest accounting; the index's
            # own index.query span nests under this root
            with obs.span("batcher.query", metric="batcher.flush_ms",
                          rows=n, top_k=top_k, estimator=estimator) as sp:
                if sp:
                    # mean over the batch's requests of flush start - join
                    wait = sp.t0 - sum(batch.joined) / len(batch.joined)
                    sp.set(requests=len(batch.joined), queue_wait_ms=1e3 * wait)
                batch.results = self.index.query(X, top_k=top_k,
                                                 estimator=estimator,
                                                 approx_ok=approx_ok)
            self._batches.inc()
            self._rows.inc(n)
            _BATCHES_TOTAL.inc()
            _ROWS_TOTAL.inc(n)
        except BaseException as e:  # propagate to every waiter, never hang
            batch.error = e
            raise
        finally:
            batch.done.set()

    def flush(self) -> None:
        """Flush every open batch (shutdown / test hook)."""
        with self._lock:
            pending = list(self._groups.items())
            self._groups.clear()
        for key, batch in pending:
            try:
                self._run(batch, key)
            except Exception:
                pass  # waiters re-raise from batch.error; keep flushing
