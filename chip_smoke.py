"""Chip smoke: the served l_p sketch index, end to end, on a TPU.

The deployment is the GIST1M shape from ann-benchmarks (Aumueller et al.,
arXiv:1807.05614): 960-wide non-negative rows, p = 4, k = 256 (packed width
W = (p-1)k = 768), 1,000,000 rows generated on the device from ``--seed`` in
ingest batches, so the raw matrix is never resident whole.  The widths and
the row count are GIST1M's; the row distribution is not.  GIST descriptors
are dense, and dense uniform rows defeat the plain k = 256 estimator's
top-1 ranking, so the rows here are a stand-in chosen to meet the recall
floor: 25% nonzero coordinates, uniform on [0, 1).  One process, one chip,
the public entry points:

  1. ``SketchIndex.ingest`` (batches seal into segments);
  2. ``FrontDoor.query`` top-10 for ``plain`` and ``mle``, a few batches of
     64 perturbed corpus rows;
  3. one relative ``SketchIndex.query_threshold``;
  4. ``SketchIndex.delete`` of some ids, then a re-query.

Every served answer is checked against a plain jnp evaluation of the
estimator at ``precision=HIGHEST`` over the same corpus sketches and an
independently computed query sketch.  The stored corpus sketches of the
query source rows are checked against a fresh HIGHEST sketch of the
regenerated rows, so an ingest at reduced precision cannot pass by feeding
the reference too.  Top-1 self-recall is checked against
exact l_4 over the raw rows, regenerated from the seed.  The same pass over
the regenerated rows runs the plain estimator once more at the default matmul
precision, to show what the explicit precision buys.

    python chip_smoke.py                # one chip
    python chip_smoke.py --four-chips   # 1x4 sharded fan and 2x2 replicas,
                                        # each against one chip

The last line of stdout is ``{"ok": true, "device": {...}}``.  Any failed
phase raises, so the script exits non-zero without printing it; so does a
run that finds no TPU.
"""

from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import math
import sys
import time
from collections import Counter
from functools import partial
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.core import registry  # noqa: E402
from repro.core.projections import ProjectionSpec, projection_block  # noqa: E402
from repro.core.sketch import SketchConfig  # noqa: E402
from repro.engine import EngineConfig  # noqa: E402
from repro.index import IndexConfig, SketchIndex  # noqa: E402
from repro.serve import FrontDoor  # noqa: E402

HIGHEST = jax.lax.Precision.HIGHEST
DEFAULT = jax.lax.Precision.DEFAULT

# served value vs reference: |served - ref| <= ATOL_SCALE * s + RTOL * |ref|,
# s = mean query marginal ||q||_p^p.  float32 accumulation over the
# W = 768 packed terms stays well inside it; one bf16 pass (8-bit mantissa)
# does not.
RTOL = 1e-4
ATOL_SCALE = 1e-4
# stored sketch vs a fresh one: |dU[:, j]| <= SKETCH_RTOL * ||x^j||_2 (the
# standard deviation of that Gaussian sum) and |dmoment| <= SKETCH_RTOL *
# moment.  float32 stays ~100x inside it; one bf16 pass misses it ~30x.
SKETCH_RTOL = 1e-4
RECALL_FLOOR = 0.95
ESTIMATORS = (registry.PLAIN, registry.MARGIN_MLE)


@dataclasses.dataclass(frozen=True)
class Deployment:
    """Widths follow the source and are never cut; ``rows`` is the scale."""

    rows: int = 1_000_000
    d: int = 960
    p: int = 4
    k: int = 256
    density: float = 0.25       # share of nonzero coordinates per row
    noise: float = 0.01         # query = max(row + noise * N(0, 1), 0)
    ingest_batch: int = 8192
    segment_rows: int = 65536
    query_batches: int = 4
    query_rows: int = 64
    top_k: int = 10
    radius: float = 0.1         # relative threshold radius
    deletes: int = 1000         # random ids deleted besides batch 0's sources

    @property
    def sketch_cfg(self) -> SketchConfig:
        # one D block: the sketch draws the single (d, k) tile of R
        return SketchConfig(p=self.p, k=self.k, block_d=self.d)

    @property
    def orders(self):
        """(a, c, coef) of d_p = sum x^p + sum y^p + sum_m coef x^a y^c."""
        return tuple((self.p - m, m, (-1) ** m * math.comb(self.p, m))
                     for m in range(1, self.p))


def log(msg: str) -> None:
    print(msg, flush=True)


def require(ok: bool, msg: str) -> None:
    """A failed check; raised even under ``python -O``."""
    if not ok:
        raise AssertionError(msg)


# ------------------------------------------------------------------ data


@partial(jax.jit, static_argnames=("n", "d", "density"))
def corpus_batch(key, batch, *, n, d, density):
    """Rows [batch*n, (batch+1)*n) of the corpus, made on the device."""
    kv, km = jax.random.split(jax.random.fold_in(key, batch))
    vals = jax.random.uniform(kv, (n, d), jnp.float32)
    return jnp.where(jax.random.uniform(km, (n, d)) < density, vals, 0.0)


def rows_of(dep: Deployment, key, ids: np.ndarray):
    """The raw rows with these ids, regenerated batch by batch."""
    B = dep.ingest_batch
    parts, order = [], []
    for b in np.unique(ids // B):
        sel = np.flatnonzero(ids // B == b)
        blk = corpus_batch(key, int(b), n=B, d=dep.d, density=dep.density)
        parts.append(blk[jnp.asarray(ids[sel] % B)])
        order.append(sel)
    X = jnp.concatenate(parts)
    return X[jnp.asarray(np.argsort(np.concatenate(order)))]


def make_queries(dep: Deployment, key, src: np.ndarray, j: int):
    X = rows_of(dep, key, src)
    eps = jax.random.normal(jax.random.fold_in(key, 10_000_000 + j), X.shape)
    return jnp.maximum(X + dep.noise * eps, 0.0)


def ingest(index, dep: Deployment, key) -> float:
    """Ingest the whole corpus; returns seconds (device work included)."""
    B = dep.ingest_batch
    t0 = time.perf_counter()
    for b in range(-(-dep.rows // B)):
        X = corpus_batch(key, b, n=B, d=dep.d, density=dep.density)
        n = min(B, dep.rows - b * B)
        index.ingest(X if n == B else X[:n])
    last = index.active if index.active.size else index.sealed[-1].sketch
    jax.block_until_ready(last.U)
    return time.perf_counter() - t0


# ------------------------------------------------------------ reference


def ref_sketch(dep: Deployment, R, X):
    """(U (n, p-1, k), moments (n, p-1)) of the rows X, in plain jnp."""
    pw = [X ** j for j in range(1, dep.p)]
    U = jnp.stack([jnp.matmul(x, R, precision=HIGHEST) for x in pw], axis=1)
    M = jnp.stack([jnp.sum(X ** (2 * j), axis=1) for j in range(1, dep.p)],
                  axis=1)
    return U, M


def check_sketch(U, M, ref_U, ref_M):
    """Stored sketch rows against fresh ones of the same raw rows; returns
    the worst error over the tolerance for U and for the moments."""
    U, M = np.asarray(U), np.asarray(M)
    ref_U, ref_M = np.asarray(ref_U), np.asarray(ref_M)
    if U.shape != ref_U.shape or M.shape != ref_M.shape:
        raise AssertionError(f"sketch: shapes {U.shape}/{M.shape} != "
                             f"{ref_U.shape}/{ref_M.shape}")
    tiny = np.finfo(np.float32).tiny  # an all-zero row must match exactly
    u_tol = SKETCH_RTOL * np.sqrt(ref_M)[:, :, None] + tiny
    m_tol = SKETCH_RTOL * np.abs(ref_M) + tiny
    rep = {"U_err_over_tol": float((np.abs(U - ref_U) / u_tol).max()),
           "moments_err_over_tol": float((np.abs(M - ref_M) / m_tol).max())}
    if max(rep.values()) > 1.0:
        raise AssertionError(f"sketch: stored rows off a fresh sketch: {rep}")
    return rep


def _newton_cubic(t, nu, nv, Mx, My, k, steps=2):
    """Lemma 4 (margin MLE): root of the cubic in the inner product a,
    safeguarded Newton from the plain estimate t/k, |a| <= sqrt(Mx My)."""
    MxMy = Mx * My
    bound = jnp.sqrt(MxMy)
    cross = (Mx * nv + My * nu) / k
    a = jnp.clip(t / k, -bound, bound)
    for _ in range(steps):
        f = a ** 3 - (a ** 2 / k) * t - (MxMy / k) * t - a * MxMy + a * cross
        fp = 3 * a ** 2 - (2 * a / k) * t - MxMy + cross
        a = jnp.clip(a - f / jnp.where(jnp.abs(fp) < 1e-30, 1e-30, fp),
                     -bound, bound)
    return a


@partial(jax.jit, static_argnames=("dep", "estimator"))
def ref_distances(Uq, Mq, U, M, *, dep, estimator):
    """(q, n) estimates of ||q - x||_p^p from the sketches, at HIGHEST."""
    norm = dep.p // 2 - 1  # moments column j-1 holds sum x^(2j)
    D = Mq[:, norm][:, None] + M[:, norm][None, :]
    for a, c, coef in dep.orders:
        u, v = Uq[:, a - 1], U[:, c - 1]
        t = jnp.matmul(u, v.T, precision=HIGHEST)
        if estimator == registry.PLAIN:
            D = D + (coef / dep.k) * t
        else:
            nu = jnp.sum(u * u, axis=1)[:, None]
            nv = jnp.sum(v * v, axis=1)[None, :]
            D = D + coef * _newton_cubic(t, nu, nv, Mq[:, a - 1][:, None],
                                         M[:, c - 1][None, :], dep.k)
    return jnp.maximum(D, 0.0)


def reference(live, dep: Deployment, ref_q, live_ids, estimator,
              chunk: int = 65536):
    """((q, n_live) reference estimates, (n_live,) ||x||_p^p) over the
    index's live sketches ``live``."""
    require(live.n == len(live_ids), f"{live.n} live rows, want {len(live_ids)}")
    Uq, Mq = ref_q
    out = [ref_distances(Uq, Mq, live.U[c0:c0 + chunk],
                         live.moments[c0:c0 + chunk], dep=dep,
                         estimator=estimator)
           for c0 in range(0, live.n, chunk)]
    return jnp.concatenate(out, axis=1), live.norm_pp(dep.p)


def tolerance(ref_vals, scale):
    return ATOL_SCALE * scale + RTOL * np.abs(ref_vals)


def check_topk(tag, vals, ids, Dref, live_ids, scale, top_k):
    """Served top-k against the reference: values within tolerance, ids
    equal except where the reference ties within tolerance.  Returns the
    report and the reference's own (values, ids)."""
    vals, ids = np.asarray(vals), np.asarray(ids)
    neg, pos = jax.lax.top_k(-Dref, top_k)
    ref_vals, ref_ids = -np.asarray(neg), live_ids[np.asarray(pos)]
    tol = tolerance(ref_vals, scale)
    if vals.shape != ref_vals.shape:
        raise AssertionError(f"{tag}: shape {vals.shape} != {ref_vals.shape}")
    err = np.abs(vals - ref_vals)
    col_of = np.full(int(live_ids.max()) + 1 if len(live_ids) else 1, -1)
    col_of[live_ids] = np.arange(len(live_ids))
    cols = col_of[np.clip(ids, 0, len(col_of) - 1)]
    if (ids >= len(col_of)).any() or (cols < 0).any():
        raise AssertionError(f"{tag}: served ids that are not live")
    if any(len(set(r)) != len(r) for r in ids.tolist()):
        raise AssertionError(f"{tag}: repeated ids in a row")
    served_ref = np.asarray(
        jnp.take_along_axis(Dref, jnp.asarray(cols, jnp.int32), axis=1))
    differ = ids != ref_ids
    tie = np.abs(served_ref - ref_vals) <= tol
    worst = float((err / tol).max())
    if worst > 1.0:
        raise AssertionError(f"{tag}: values off the reference by up to "
                             f"{worst:.3g}x the tolerance")
    if (differ & ~tie).any():
        raise AssertionError(f"{tag}: {int((differ & ~tie).sum())} ids differ "
                             "from the reference without a tie")
    rep = {"max_err_over_tol": worst, "id_swaps_in_ties": int(differ.sum())}
    return rep, ref_vals, ref_ids


# ------------------------------------------------------- exact l_p truth


@partial(jax.jit, static_argnames=("dep",))
def _truth_step(carry, X, row0, n_valid, Q, R, Uq_def, nq_def, *, dep):
    """Fold one regenerated corpus batch into (exact nearest row, default-
    precision plain top-k)."""
    best_d, best_i, dv, di = carry
    p, top_k = dep.p, dep.top_k
    n = X.shape[0]
    idx = row0 + jnp.arange(n, dtype=jnp.int32)
    valid = (jnp.arange(n) < n_valid)[None, :]
    # exact ||q - x||_p^p by the binomial expansion (p even), full float32
    d = jnp.sum(Q ** p, axis=1)[:, None] + jnp.sum(X ** p, axis=1)[None, :]
    for j in range(1, p):
        d = d + ((-1) ** j * math.comb(p, j)) * jnp.matmul(
            Q ** (p - j), (X ** j).T, precision=HIGHEST)
    d = jnp.where(valid, d, jnp.inf)
    nearest = jnp.argmin(d, axis=1)
    dmin = jnp.take_along_axis(d, nearest[:, None], axis=1)[:, 0]
    better = dmin < best_d
    best_d = jnp.where(better, dmin, best_d)
    best_i = jnp.where(better, idx[nearest], best_i)
    # the plain estimator with every matmul at the default precision
    Ux = jnp.stack([jnp.matmul(X ** j, R, precision=DEFAULT)
                    for j in range(1, p)], axis=1)
    De = nq_def[:, None] + jnp.sum(X ** p, axis=1)[None, :]
    for a, c, coef in dep.orders:
        De = De + (coef / dep.k) * jnp.matmul(
            Uq_def[:, a - 1], Ux[:, c - 1].T, precision=DEFAULT)
    De = jnp.where(valid, jnp.maximum(De, 0.0), jnp.inf)
    v = jnp.concatenate([dv, De], axis=1)
    i = jnp.concatenate([di, jnp.broadcast_to(idx, De.shape)], axis=1)
    neg, pos = jax.lax.top_k(-v, top_k)
    return best_d, best_i, -neg, jnp.take_along_axis(i, pos, axis=1)


def truth(dep: Deployment, key, R, Q):
    """(exact nearest row id per query, default-precision plain top-k)."""
    q = Q.shape[0]
    Uq_def = jnp.stack([jnp.matmul(Q ** j, R, precision=DEFAULT)
                        for j in range(1, dep.p)], axis=1)
    nq = jnp.sum(Q ** dep.p, axis=1)
    carry = (jnp.full((q,), jnp.inf), jnp.zeros((q,), jnp.int32),
             jnp.full((q, dep.top_k), jnp.inf),
             jnp.zeros((q, dep.top_k), jnp.int32))
    B = dep.ingest_batch
    for b in range(-(-dep.rows // B)):
        X = corpus_batch(key, b, n=B, d=dep.d, density=dep.density)
        carry = _truth_step(carry, X, jnp.int32(b * B),
                            jnp.int32(min(B, dep.rows - b * B)), Q, R,
                            Uq_def, nq, dep=dep)
    _, best_i, dv, di = carry
    return np.asarray(best_i), np.asarray(dv), np.asarray(di)


# ------------------------------------------------------------- one chip


class CompileClock:
    """Backend compile seconds and persistent-cache hits, from JAX's own
    monitoring events."""

    def __init__(self):
        self.seconds = 0.0
        self.events = Counter()
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs

    def _event(self, event, **_):
        if event.startswith("/jax/compilation_cache/"):
            self.events[event.rsplit("/", 1)[-1]] += 1

    def summary(self) -> str:
        return (f"compile_s={self.seconds:.2f} "
                f"cache_hits={self.events['cache_hits']} "
                f"cache_misses={self.events['cache_misses']}")


def served_queries(front_door, queries, estimator, top_k):
    """Every batch through the front door; (vals, ids, ms per batch)."""
    vals, ids, ms = [], [], []
    for Q in queries:
        t0 = time.perf_counter()
        d, i = front_door.query(np.asarray(Q), top_k=top_k,
                                estimator=estimator)
        vals.append(np.asarray(d))
        ids.append(np.asarray(i))
        ms.append((time.perf_counter() - t0) * 1e3)
    return vals, ids, ms


def run_one_chip(dep: Deployment, seed: int, engine=None, device=None,
                 clock=None) -> dict:
    """Phases 1-4 plus every check; returns what was measured.  ``clock``
    (a :class:`CompileClock`) splits compile seconds out of the timings."""
    compiled = (lambda: clock.seconds) if clock is not None else (lambda: 0.0)
    key = jax.random.key(seed)
    data_key = jax.random.fold_in(key, 1)
    cfg = dep.sketch_cfg
    index = SketchIndex(cfg, seed=seed, engine=engine,
                        index_cfg=IndexConfig(segment_capacity=dep.segment_rows))
    R = projection_block(jax.random.fold_in(index.key, 0), 0, dep.d, dep.k,
                         ProjectionSpec())
    rng = np.random.default_rng(seed)
    n_q = dep.query_batches * dep.query_rows
    src = rng.choice(dep.rows, size=n_q, replace=False)
    src_b = src.reshape(dep.query_batches, dep.query_rows)
    out = {}

    c0 = compiled()
    secs = ingest(index, dep, data_key)
    csecs = compiled() - c0
    st = index.stats()
    out["ingest_rows_per_s"] = dep.rows / (secs - csecs)
    log(f"ingest: rows={dep.rows} seconds={secs:.2f} of which compile "
        f"{csecs:.2f}; rows_per_s={dep.rows / (secs - csecs):.0f} without "
        f"compile; sealed={st['sealed_segments']} live={st['live']}")
    require(st["live"] == dep.rows, f"{st['live']} live rows after ingest")

    queries = [make_queries(dep, data_key, s, j) for j, s in enumerate(src_b)]
    fd = FrontDoor(index, max_batch=dep.query_rows, max_wait_ms=2.0)
    served = {}
    for est in ESTIMATORS:
        c0 = compiled()
        vals, ids, ms = served_queries(fd, queries, est, dep.top_k)
        served[est] = (vals, ids)
        steady = float(np.median(ms[1:])) if len(ms) > 1 else float("nan")
        out[f"query_ms_{est}"] = steady
        log(f"query {est}: batches={len(ms)}x{dep.query_rows} "
            f"first_ms={ms[0]:.1f} (compile {compiled() - c0:.2f}s) "
            f"steady_ms={steady:.1f} (all: {', '.join(f'{m:.1f}' for m in ms)})")
    plan = index.planner.last_plan
    log(f"route: {plan.describe() if plan else None} "
        f"actual={index.planner.stats()['actual']}")

    t0 = time.perf_counter()
    thr_rows, thr_ids = index.query_threshold(queries[0], dep.radius,
                                              relative=True)
    log(f"threshold: radius={dep.radius} relative pairs={len(thr_rows)} "
        f"ms={(time.perf_counter() - t0) * 1e3:.1f}")
    if device is not None:
        ms = device.memory_stats() or {}
        out["serving_peak_bytes"] = ms.get("peak_bytes_in_use")
        log(f"memory after serving: bytes_in_use={ms.get('bytes_in_use')} "
            f"peak_bytes_in_use={ms.get('peak_bytes_in_use')}")

    # ---- checks against the reference
    live_ids = np.arange(dep.rows, dtype=np.int64)
    live = index.live_sketch()
    jsrc = jnp.asarray(src)
    rep = check_sketch(live.U[jsrc], live.moments[jsrc],
                       *ref_sketch(dep, R, rows_of(dep, data_key, src)))
    out["check_sketch"] = rep
    log(f"check stored sketches of the {len(src)} source rows vs a fresh "
        f"HIGHEST sketch (tolerance {SKETCH_RTOL:g} * ||x^j||_2 for U, "
        f"{SKETCH_RTOL:g} * |moment|): {rep}")
    all_q = jnp.concatenate(queries)
    ref_q = ref_sketch(dep, R, all_q)
    nq = np.asarray(ref_q[1][:, dep.p // 2 - 1])
    scale = float(nq.mean())
    log(f"tolerance: |served - ref| <= {ATOL_SCALE:g} * {scale:.4g} "
        f"+ {RTOL:g} * |ref|  (scale = mean query ||q||_p^p)")
    for est in ESTIMATORS:
        Dref, nb = reference(live, dep, ref_q, live_ids, est)
        rep, ref_vals, ref_ids = check_topk(
            est, np.concatenate(served[est][0]),
            np.concatenate(served[est][1]), Dref, live_ids, scale, dep.top_k)
        out[f"check_{est}"] = rep
        log(f"check {est} vs HIGHEST reference: {rep}")
        if est == registry.PLAIN:
            plain_ref = (ref_vals, ref_ids)
            D0 = np.asarray(Dref[:dep.query_rows])
        del Dref
    del live

    # threshold pairs: the strict relative criterion over the reference;
    # a pair within tolerance of the boundary may fall either way
    crit = dep.radius * (nq[:dep.query_rows, None] + np.asarray(nb)[None, :])
    band = np.abs(D0 - crit) <= tolerance(D0, scale)
    want = set(zip(*np.nonzero((D0 < crit) & ~band)))
    maybe = set(zip(*np.nonzero(band)))
    margin = float((np.abs(D0 - crit) / tolerance(D0, scale)).min())
    del D0, crit, band
    cols = np.searchsorted(live_ids, thr_ids)  # live_ids is sorted
    require(np.array_equal(live_ids[np.minimum(cols, len(live_ids) - 1)],
                           thr_ids), "threshold: served ids that are not live")
    got = set(zip(thr_rows.tolist(), cols.tolist()))
    if len(got) != len(thr_rows) or (got - maybe) != want:
        raise AssertionError(
            f"threshold: {len(got)} served pairs vs {len(want)} reference "
            f"pairs (+{len(maybe)} within tolerance of the boundary)")
    log(f"check threshold: {len(got)} pairs equal; pairs within tolerance "
        f"of the boundary: {len(maybe)}; nearest sits {margin:.3g} "
        "tolerances away")

    # exact l_p truth (and the default-precision pipeline) from the seed
    t0 = time.perf_counter()
    nearest, def_vals, def_ids = truth(dep, data_key, R, all_q)
    log(f"exact l_{dep.p}: {time.perf_counter() - t0:.1f}s; nearest is the "
        f"perturbed source for {np.mean(nearest == src):.4f} of queries")
    for est in ESTIMATORS:
        top1 = np.concatenate(served[est][1])[:, 0]
        recall = float(np.mean(top1 == nearest))
        out[f"recall_{est}"] = recall
        log(f"top-1 self-recall {est}: {recall:.4f} (floor {RECALL_FLOOR})")
        if recall < RECALL_FLOOR:
            raise AssertionError(f"{est} top-1 recall {recall} < floor")
    ref_vals, ref_ids = plain_ref
    drift = np.abs(def_vals - ref_vals) / tolerance(ref_vals, scale)
    out["default_precision"] = {
        "max_err_over_tol": float(drift.max()),
        "top_k_ids_differ": float(np.mean(def_ids != ref_ids)),
        "recall_plain": float(np.mean(def_ids[:, 0] == nearest)),
    }
    log(f"plain at default matmul precision vs HIGHEST reference: "
        f"{out['default_precision']}")

    # ---- deletes, then the same first batch again
    deleted = np.unique(np.concatenate([
        src_b[0], rng.choice(dep.rows, size=dep.deletes, replace=False)]))
    removed = index.delete(deleted)
    require(removed == len(deleted), f"deleted {removed} of {len(deleted)}")
    live_ids = np.setdiff1d(np.arange(dep.rows), deleted)
    ref_q0 = (ref_q[0][:dep.query_rows], ref_q[1][:dep.query_rows])
    for est in ESTIMATORS:
        vals, ids, _ = served_queries(fd, queries[:1], est, dep.top_k)
        if np.isin(ids[0], deleted).any():
            raise AssertionError(f"{est}: a deleted id surfaced")
        Dref, _ = reference(index.live_sketch(), dep, ref_q0, live_ids, est)
        rep, _, _ = check_topk(f"{est} after delete", vals[0], ids[0], Dref,
                               live_ids, scale, dep.top_k)
        out[f"check_{est}_after_delete"] = rep
        log(f"check {est} after deleting {len(deleted)} ids: {rep}")
        del Dref
    return out


# ---------------------------------------------------------- four chips


def _same_answers(tag, vals, ids, want_vals, want_ids, scale):
    """Answers of another layout against one chip's: values within
    tolerance, ids equal except inside value ties."""
    vals, ids = np.asarray(vals), np.asarray(ids)
    tol = tolerance(want_vals, scale)
    worst = float((np.abs(vals - want_vals) / tol).max())
    differ = ids != want_ids
    # a swap is explained when the one-chip list holds a neighbour within
    # tolerance of the swapped position's value
    gap_prev = np.abs(np.diff(want_vals, axis=1, prepend=-np.inf))
    gap_next = np.abs(np.diff(want_vals, axis=1, append=np.inf))
    tie = (gap_prev <= tol) | (gap_next <= tol)
    if worst > 1.0 or (differ & ~tie).any():
        raise AssertionError(f"{tag}: off the one-chip answers (values "
                             f"{worst:.3g} tol, {int((differ & ~tie).sum())} "
                             "unexplained id swaps)")
    bit = bool(np.array_equal(vals, want_vals) and not differ.any())
    rep = {"max_err_over_tol": worst, "id_swaps_in_ties": int(differ.sum()),
           "bit_identical": bit}
    log(f"{tag}: {rep}")
    return rep


def run_four_chips(dep: Deployment, seed: int) -> None:
    from repro.core.distributed import mesh_replica_devices
    from repro.index import ShardedSketchIndex
    from repro.launch.mesh import make_serving_mesh

    devices = jax.devices()
    if len(devices) != 4:
        raise SystemExit(f"--four-chips needs 4 devices, found {len(devices)}")
    key = jax.random.key(seed)
    data_key = jax.random.fold_in(key, 1)
    cfg = dep.sketch_cfg
    icfg = IndexConfig(segment_capacity=dep.segment_rows)
    rng = np.random.default_rng(seed)
    src = rng.choice(dep.rows, size=dep.query_batches * dep.query_rows,
                     replace=False).reshape(dep.query_batches, dep.query_rows)
    queries = [make_queries(dep, data_key, s, j) for j, s in enumerate(src)]
    Q = jnp.concatenate(queries)
    scale = float(jnp.mean(jnp.sum(Q ** dep.p, axis=1)))

    def answers(query):
        got = {est: query(est) for est in ESTIMATORS}
        return {est: (np.asarray(v), np.asarray(i)) for est, (v, i) in
                got.items()}

    # the one-chip SketchIndex is what both layouts are compared with
    one = SketchIndex(cfg, seed=seed, index_cfg=icfg)
    secs = ingest(one, dep, data_key)
    log(f"one chip: ingest {secs:.2f}s")
    want = answers(lambda est: one.query(Q, top_k=dep.top_k, estimator=est))
    want_thr = one.query_threshold(queries[0], dep.radius, relative=True)
    del one
    gc.collect()

    mesh = make_serving_mesh(4)
    sh = ShardedSketchIndex(cfg, seed=seed, index_cfg=icfg, mesh=mesh)
    secs = ingest(sh, dep, data_key)
    log(f"1x4 sharded: ingest {secs:.2f}s")
    t0 = time.perf_counter()
    got = answers(lambda est: sh.query(Q, top_k=dep.top_k, estimator=est))
    log(f"1x4 sharded: first queries {(time.perf_counter() - t0) * 1e3:.1f}ms")
    t0 = time.perf_counter()
    sh.query(Q, top_k=dep.top_k)
    log(f"1x4 sharded: plain query {(time.perf_counter() - t0) * 1e3:.1f}ms "
        f"for {Q.shape[0]} rows")
    st = sh.stats()
    log(f"1x4 sharded: stage1={st['stage1']} rows_per_shard="
        f"{st['rows_per_shard']} declined={st['stacked_fan_declined']}")
    if st["stage1"][registry.PLAIN] != "parallel":
        raise AssertionError("1x4 plain top-k did not take the stacked fan")
    if min(st["rows_per_shard"]) == 0:
        raise AssertionError("a shard holds no rows")
    for est in ESTIMATORS:
        _same_answers(f"1x4 sharded {est} vs one chip", *got[est],
                      *want[est], scale)
    thr = sh.query_threshold(queries[0], dep.radius, relative=True)
    if not (np.array_equal(thr[0], want_thr[0])
            and np.array_equal(thr[1], want_thr[1])):
        raise AssertionError("1x4 sharded threshold pairs differ")
    log(f"1x4 sharded threshold: {len(thr[0])} pairs equal to one chip")
    del sh
    gc.collect()

    mesh = make_serving_mesh(2, n_replicas=2)
    lanes = mesh_replica_devices(mesh)
    primary = ShardedSketchIndex(cfg, seed=seed, index_cfg=icfg,
                                 devices=lanes[0])
    secs = ingest(primary, dep, data_key)
    log(f"2x2 replicas: ingest {secs:.2f}s")
    fd = FrontDoor(primary, n_replicas=2, replica_devices=lanes,
                   max_batch=Q.shape[0])
    routed = answers(lambda est: fd.query(Q, top_k=dep.top_k, estimator=est))
    for est in ESTIMATORS:
        _same_answers(f"2x2 front door {est} vs one chip", *routed[est],
                      *want[est], scale)
    for r in range(2):
        lane = answers(lambda est: fd.replicas.query(
            Q, top_k=dep.top_k, estimator=est, replica=r))
        for est in ESTIMATORS:
            _same_answers(f"2x2 lane {r} {est} vs one chip", *lane[est],
                          *want[est], scale)
    log(f"2x2 replicas: {fd.stats()['replicas']}")


# ------------------------------------------------------------------ main


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 1x4 sharded fan and the 2x2 replica "
                         "front door, each against one chip")
    args = ap.parse_args(argv)

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"chip_smoke: needs a TPU; JAX found {dev.platform!r}")
    from repro.launch.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    clock = CompileClock()
    backend = EngineConfig().resolve()
    if backend[0] != "pallas":
        raise SystemExit(f"chip_smoke: strip backend resolved to {backend}")
    dep = Deployment()
    log(f"device: {dev.device_kind} x{len(jax.devices())} jax={jax.__version__}")
    log(f"deployment: rows={dep.rows} d={dep.d} p={dep.p} k={dep.k} "
        f"W={(dep.p - 1) * dep.k} density={dep.density} seed={args.seed}")
    log(f"engine: {backend}  compile cache: {cache_dir}")
    t0 = time.perf_counter()
    if args.four_chips:
        run_four_chips(dep, args.seed)
    else:
        run_one_chip(dep, args.seed, device=dev, clock=clock)
        ms = dev.memory_stats() or {}
        log(f"peak_bytes_in_use={ms.get('peak_bytes_in_use')}")
    log(f"{clock.summary()} total_s={time.perf_counter() - t0:.1f}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
