"""Index serving benchmark: ingest throughput + query latency percentiles.

Emits the harness CSV rows (name,us_per_call,derived):

  index_ingest        us per ingest(batch) call    derived = rows_per_s
  index_query         us per query(top_k) call     derived = p50_ms|p95_ms
  index_query_mb      us per micro-batched row     derived = rows_per_s (batched)
  index_query_sharded us per sharded query call    derived = p50_ms|shards
                      (with --mesh / REPRO_BENCH_MESH=1: segments spread over
                      a 1xN serving mesh, two-stage fan)
  stage1_parallel     us per pre-sketched sharded query through the
                      shard_map stage-1 fan, derived =
                      p50_ms|dispatch_ms|shards — dispatch_ms is the same
                      pre-sketched query through the sequential-dispatch
                      stage 1, so the row doubles as the parallel-fan
                      speedup readout (gated by the CI baseline check)
  threshold_parallel  us per pre-sketched sharded threshold query through
                      the stacked shard_map fan, derived =
                      p50_ms|dispatch_ms|hits — dispatch_ms is the same
                      query through the sequential-dispatch scan; pairs are
                      self-checked identical before timing
  planner_routing     us per pre-sketched mle query under approx_ok through
                      the planner's stacked shard_map route, derived =
                      p50_ms|dispatch_ms|gates — dispatch_ms is the same
                      query through the exact dispatch fan; the module
                      asserts the conformance gate passed AND that the
                      stacked route beats dispatch (best-of-reps), so the
                      approx opt-in provably buys latency
  obs_overhead        us per pre-sketched query with span tracing ENABLED,
                      derived = ratio|off_us — ratio is enabled/disabled on
                      interleaved min-of-reps and is asserted <= 1.10 inside
                      this module (hardware-independent), so the CI smoke
                      fails if the observability layer stops being ~free
  front_door          us per fully-scheduled query through the SLO front
                      door (admission + deadline + micro-batch + 2-replica
                      routing), derived = p50_ms|admitted|shed|replicas —
                      answers are asserted bit-identical to the bare index
                      and one starved tenant must shed with a typed
                      Overloaded before timing starts
  stable_ingest       us per fractional-p (p=1.5, α-stable) ingest batch
                      through the stable_sparse gather path, derived =
                      rows_per_s|dense_us — dense_us is the same corpus
                      ingested through the dense stable family, and the
                      gather vs scatter-materialized tiles are asserted
                      allclose before timing starts
  rebalance           us per skew-healing migration pass (skewed corpus:
                      heavy deletes on most shards, compact, rebalance),
                      derived = moved|skew_before|skew_after

REPRO_BENCH_TINY=1 shrinks shapes for the CI smoke job.
"""

import os
import sys
import time

import jax.numpy as jnp
import numpy as np

from benchmarks.common import emit
from repro.core import SketchConfig
from repro.index import IndexConfig, ShardedSketchIndex, SketchIndex

TINY = os.environ.get("REPRO_BENCH_TINY") == "1"


def _mesh_enabled() -> bool:
    return "--mesh" in sys.argv or os.environ.get("REPRO_BENCH_MESH") == "1"


def run():
    n, d, k, cap = ((2048, 1024, 64, 512) if TINY else
                    (16384, 8192, 256, 4096))
    batch, q, top_k = (128 if TINY else 512), 16, 10
    rng = np.random.default_rng(0)
    X = rng.uniform(0, 1, (n, d)).astype(np.float32)
    index = SketchIndex(
        SketchConfig(p=4, k=k, block_d=min(1024, d)),
        index_cfg=IndexConfig(segment_capacity=cap),
    )

    # warmup: compile sketch + writer for the batch shape
    index.ingest(jnp.asarray(X[:batch]))
    t0 = time.perf_counter()
    for lo in range(batch, n, batch):
        index.ingest(jnp.asarray(X[lo:lo + batch]))
    dt = time.perf_counter() - t0
    ingest_us = dt / max((n - batch) // batch, 1) * 1e6
    rows_per_s = (n - batch) / dt

    Q = jnp.asarray(X[:q] + 0.01 * rng.standard_normal((q, d)).astype(np.float32))
    index.query(Q, top_k=top_k)  # warmup
    lat = []
    for _ in range(3 if TINY else 10):
        t0 = time.perf_counter()
        index.query(Q, top_k=top_k)
        lat.append((time.perf_counter() - t0) * 1e3)
    lat = np.sort(np.asarray(lat))
    p50 = float(np.percentile(lat, 50))
    p95 = float(np.percentile(lat, 95))

    # one fused pass over 4x the rows ~= the micro-batcher's coalesced shape
    Qb = jnp.concatenate([Q] * 4, axis=0)
    index.query(Qb, top_k=top_k)
    t0 = time.perf_counter()
    reps = 3 if TINY else 10
    for _ in range(reps):
        index.query(Qb, top_k=top_k)
    per_row_us = (time.perf_counter() - t0) / (reps * Qb.shape[0]) * 1e6

    rows = [
        ("index_ingest", ingest_us, f"rows_per_s={rows_per_s:.0f}"),
        ("index_query", p50 * 1e3, f"p50_ms={p50:.2f}|p95_ms={p95:.2f}"),
        ("index_query_mb", per_row_us,
         f"rows_per_s={1e6 / max(per_row_us, 1e-9):.0f}"),
    ]

    # tracing-enabled vs disabled over the same pre-sketched query: the
    # observability layer must be ~free.  Each rep times the two modes
    # back-to-back and the gate takes the MIN of the per-pair ratios: a
    # noisy rep inflates both sides of its own pair (common-mode, cancels),
    # while a real systematic overhead shows up in EVERY pair — so the min
    # stays high only when tracing genuinely costs.  The ratio (unlike the
    # absolute row) is hardware-independent, so it is asserted HERE, in the
    # module, not just gated by the baseline numbers.
    from repro import obs
    from repro.core.sketch import sketch as sketch_rows

    qsk = sketch_rows(Q, index.key, index.cfg)
    index.query_sketch(qsk, top_k=top_k)  # warm the jit caches
    t_off, t_on = [], []
    try:
        for _ in range(12 if TINY else 20):
            t0 = time.perf_counter()
            index.query_sketch(qsk, top_k=top_k)
            t_off.append(time.perf_counter() - t0)
            obs.enable()
            t0 = time.perf_counter()
            index.query_sketch(qsk, top_k=top_k)
            t_on.append(time.perf_counter() - t0)
            obs.disable()
    finally:
        obs.disable()
    us_off, us_on = min(t_off) * 1e6, min(t_on) * 1e6
    ratio = min(on / off for on, off in zip(t_on, t_off))
    assert ratio <= 1.10, (
        f"tracing-enabled query is >= {ratio:.3f}x the disabled path in "
        f"every interleaved pair ({us_on:.0f}us vs {us_off:.0f}us at best): "
        f"the obs layer must stay ~free")
    rows.append(("obs_overhead", us_on,
                 f"ratio={ratio:.3f}|off_us={us_off:.0f}"))

    # the SLO front door end to end: admission -> deadline -> micro-batch ->
    # replica lane, on the same corpus.  Answers are asserted bit-identical
    # to the bare index first (the scheduler must never change results),
    # then the row times fully-scheduled queries under a generous deadline;
    # one deliberately starved tenant proves the typed-shedding path costs
    # (and serves) nothing
    from repro.serve import FrontDoor, Overloaded, TenantQuota

    fd = FrontDoor(index, n_replicas=2, max_wait_ms=1.0,
                   tenant_quotas={"starved": TenantQuota(rate=1e-6,
                                                         burst=1e-3)})
    want = index.query(Q, top_k=top_k)
    got = fd.query(np.asarray(Q), top_k=top_k, deadline_ms=60_000.0)  # warmup
    assert np.array_equal(np.asarray(want[0]), np.asarray(got[0]))
    assert np.array_equal(want[1], got[1])
    try:
        fd.query(np.asarray(Q), top_k=top_k, tenant="starved")
        raise AssertionError("starved tenant must shed, not serve")
    except Overloaded as e:
        assert e.reason == "quota" and e.retry_after_ms > 0
    lat = []
    for _ in range(3 if TINY else 10):
        t0 = time.perf_counter()
        fd.query(np.asarray(Q), top_k=top_k, deadline_ms=60_000.0)
        lat.append((time.perf_counter() - t0) * 1e3)
    p50f = float(np.percentile(np.asarray(lat), 50))
    sched = fd.stats()["scheduler"]
    assert sched["shed"] == 1 and sched["deadline_exceeded"] == 0
    rows.append(("front_door", p50f * 1e3,
                 f"p50_ms={p50f:.2f}|admitted={sched['admitted']}"
                 f"|shed={sched['shed']}|replicas=2"))

    # fractional-p ingest: α-stable sketches (p=1.5) through the same index
    # write path.  The stable_sparse family gathers nnz (index, value)
    # pairs per D-block instead of the dense (block_d x k) matmul; the row
    # times the sparse ingest with the dense-family ingest in derived.
    # Parity first: the gather ingest and the dense scatter-materialized
    # tiles must describe the same R (equal up to fp re-association)
    from repro.core import ProjectionSpec
    from repro.kernels.power_project.ops import sketch_via_kernel

    bd = min(1024, d)
    s_cfg = SketchConfig(p=1.5, k=k, block_d=bd,
                         projection=ProjectionSpec(family="stable_sparse",
                                                   block_d=bd))
    dn_cfg = SketchConfig(p=1.5, k=k, block_d=bd,
                          projection=ProjectionSpec(family="stable",
                                                    block_d=bd))
    s_idx = SketchIndex(s_cfg, index_cfg=IndexConfig(segment_capacity=cap))
    dn_idx = SketchIndex(dn_cfg, index_cfg=IndexConfig(segment_capacity=cap))
    gat = sketch_rows(jnp.asarray(X[:batch]), s_idx.key, s_cfg)
    # the interpreter runs on any platform; this check is about R, not speed
    sca = sketch_via_kernel(jnp.asarray(X[:batch]), s_idx.key, s_cfg,
                            interpret=True)
    np.testing.assert_allclose(np.asarray(gat.U), np.asarray(sca.U),
                               rtol=2e-4, atol=2e-4)
    s_idx.ingest(jnp.asarray(X[:batch]))   # warmup: compile both write paths
    dn_idx.ingest(jnp.asarray(X[:batch]))
    t_sp, t_dn = [], []
    for lo in range(batch, n, batch):
        xb = jnp.asarray(X[lo:lo + batch])
        t0 = time.perf_counter()
        s_idx.ingest(xb)
        t_sp.append(time.perf_counter() - t0)
        t0 = time.perf_counter()
        dn_idx.ingest(xb)
        t_dn.append(time.perf_counter() - t0)
    sparse_us = float(np.sum(t_sp)) / max(len(t_sp), 1) * 1e6
    dense_us = float(np.sum(t_dn)) / max(len(t_dn), 1) * 1e6
    rows.append(("stable_ingest", sparse_us,
                 f"rows_per_s={batch / max(sparse_us, 1e-9) * 1e6:.0f}"
                 f"|dense_us={dense_us:.0f}"))

    if _mesh_enabled():
        # sharded smoke: same corpus spread over the 1xN serving mesh via
        # the two-stage fan; answers must match the single-host index
        from repro.launch.mesh import make_serving_mesh

        mesh = make_serving_mesh()
        sharded = ShardedSketchIndex(
            SketchConfig(p=4, k=k, block_d=min(1024, d)),
            index_cfg=IndexConfig(segment_capacity=cap), mesh=mesh,
        )
        for lo in range(0, n, batch):
            sharded.ingest(jnp.asarray(X[lo:lo + batch]))
        assert sharded.stats()["stage1"]["plain"] == "parallel"
        want = index.query(Q, top_k=top_k)
        got = sharded.query(Q, top_k=top_k)  # warmup + conformance check
        assert np.array_equal(np.asarray(want[0]), np.asarray(got[0]))
        assert np.array_equal(want[1], got[1])
        reps = 3 if TINY else 10
        lat = []
        for _ in range(reps):
            t0 = time.perf_counter()
            sharded.query(Q, top_k=top_k)
            lat.append((time.perf_counter() - t0) * 1e3)
        p50s = float(np.percentile(np.asarray(lat), 50))
        rows.append(("index_query_sharded", p50s * 1e3,
                     f"p50_ms={p50s:.2f}|shards={sharded.n_shards}"))

        # the shard_map stage-1 fan vs the sequential-dispatch stage 1 over
        # the same segments, both on a pre-sketched query — the sketch cost
        # is identical either way, so this isolates the stage-1 difference
        from repro.core.sketch import sketch as sketch_rows
        from repro.index.sharded import sharded_fan_topk

        qsk = sketch_rows(Q, sharded.key, sharded.cfg)
        par = sharded.query_sketch(qsk, top_k=top_k)  # warmup (parallel fan)
        disp = sharded_fan_topk(qsk, sharded._segments(), sharded.cfg,
                                sharded.devices, top_k=top_k,
                                engine=sharded.engine)  # warmup (dispatch)
        for dv, iv in (par, disp):
            assert np.array_equal(np.asarray(got[0]), np.asarray(dv))
            assert np.array_equal(got[1], iv)
        lat_p, lat_d = [], []
        for _ in range(reps):
            t0 = time.perf_counter()
            sharded.query_sketch(qsk, top_k=top_k)
            lat_p.append((time.perf_counter() - t0) * 1e3)
            t0 = time.perf_counter()
            sharded_fan_topk(qsk, sharded._segments(), sharded.cfg,
                             sharded.devices, top_k=top_k,
                             engine=sharded.engine)
            lat_d.append((time.perf_counter() - t0) * 1e3)
        p50p = float(np.percentile(np.asarray(lat_p), 50))
        p50d = float(np.percentile(np.asarray(lat_d), 50))
        rows.append(("stage1_parallel", p50p * 1e3,
                     f"p50_ms={p50p:.2f}|dispatch_ms={p50d:.2f}"
                     f"|shards={sharded.n_shards}"))

        # the stacked threshold fan vs the sequential-dispatch scan over the
        # same segments, pre-sketched (isolates stage 1, like stage1_parallel)
        from repro.index.sharded import sharded_threshold_scan

        radius = 0.15
        tp = sharded.query_threshold_sketch(qsk, radius=radius, relative=True)
        td = sharded_threshold_scan(qsk, sharded._segments(), sharded.cfg,
                                    sharded.devices, radius=radius,
                                    relative=True, engine=sharded.engine)
        assert np.array_equal(tp[0], td[0]) and np.array_equal(tp[1], td[1])
        lat_p, lat_d = [], []
        for _ in range(reps):
            t0 = time.perf_counter()
            sharded.query_threshold_sketch(qsk, radius=radius, relative=True)
            lat_p.append((time.perf_counter() - t0) * 1e3)
            t0 = time.perf_counter()
            sharded_threshold_scan(qsk, sharded._segments(), sharded.cfg,
                                   sharded.devices, radius=radius,
                                   relative=True, engine=sharded.engine)
            lat_d.append((time.perf_counter() - t0) * 1e3)
        p50p = float(np.percentile(np.asarray(lat_p), 50))
        p50d = float(np.percentile(np.asarray(lat_d), 50))
        rows.append(("threshold_parallel", p50p * 1e3,
                     f"p50_ms={p50p:.2f}|dispatch_ms={p50d:.2f}"
                     f"|hits={len(tp[0])}"))

        # planner routing payoff: mle under approx_ok rides the stacked
        # shard_map fan (tolerance-gated against the exact dispatch answer);
        # the row times that route vs the same pre-sketched mle query through
        # the dispatch fan and asserts the opt-in actually buys latency —
        # best-of-reps, the same de-noising the ratchet gate uses
        from repro.index import ApproxContract

        contract = ApproxContract()
        exact = sharded_fan_topk(qsk, sharded._segments(), sharded.cfg,
                                 sharded.devices, top_k=top_k,
                                 estimator="mle", engine=sharded.engine)
        # first approx query calibrates the conformance gate for this stack
        apx = sharded.query_sketch(qsk, top_k=top_k, estimator="mle",
                                   approx_ok=contract)
        assert sharded.stats()["stage1"]["mle"] == "parallel"
        gates = sharded.stats()["planner"]["approx_gates"]
        assert gates and all(g["ok"] for g in gates)
        np.testing.assert_allclose(np.asarray(apx[0]), np.asarray(exact[0]),
                                   rtol=contract.rtol, atol=contract.atol)
        lat_p, lat_d = [], []
        for _ in range(reps):
            t0 = time.perf_counter()
            sharded.query_sketch(qsk, top_k=top_k, estimator="mle",
                                 approx_ok=contract)
            lat_p.append((time.perf_counter() - t0) * 1e3)
            t0 = time.perf_counter()
            sharded_fan_topk(qsk, sharded._segments(), sharded.cfg,
                             sharded.devices, top_k=top_k, estimator="mle",
                             engine=sharded.engine)
            lat_d.append((time.perf_counter() - t0) * 1e3)
        assert min(lat_p) < min(lat_d), (
            f"approx mle on the stacked fan ({min(lat_p):.2f}ms best) must "
            f"beat the dispatch fan ({min(lat_d):.2f}ms best) — otherwise "
            "the approx_ok opt-in buys nothing")
        p50p = float(np.percentile(np.asarray(lat_p), 50))
        p50d = float(np.percentile(np.asarray(lat_d), 50))
        rows.append(("planner_routing", p50p * 1e3,
                     f"p50_ms={p50p:.2f}|dispatch_ms={p50d:.2f}"
                     f"|gates={len(gates)}"))

        # skew-healing migration pass on a 4-shard fleet (planner-level fake
        # shards so the row runs on the 1-device CI box): tombstone most rows
        # of every segment off shard 0, compact (delete skew becomes height
        # skew), then time the rebalance that levels the stacked heights
        import jax

        n_fake = 4
        cap_r = max(cap // n_fake, 64)
        reb = ShardedSketchIndex(
            SketchConfig(p=4, k=k, block_d=min(1024, d)),
            index_cfg=IndexConfig(segment_capacity=cap_r),
            devices=[jax.devices()[0]] * n_fake,
        )
        ids = np.concatenate([reb.ingest(jnp.asarray(X[lo:lo + batch]))
                              for lo in range(0, n, batch)])
        seg_of = np.arange(n) // cap_r
        kill = np.flatnonzero(seg_of % n_fake != 0)
        kill = np.setdiff1d(kill, kill[::16])  # leave survivors to migrate
        reb.delete(ids[kill])
        reb.compact(min_live_frac=0.95)
        skew_before = reb.stats()["shard_skew"]
        t0 = time.perf_counter()
        moved = reb.rebalance(skew_trigger=1.2)
        reb_us = (time.perf_counter() - t0) * 1e6
        skew_after = reb.stats()["shard_skew"]
        assert moved > 0 and skew_after < skew_before
        rows.append(("rebalance", reb_us,
                     f"moved={moved}|skew_before={skew_before:.2f}"
                     f"|skew_after={skew_after:.2f}"))

    emit(rows)


if __name__ == "__main__":
    print("name,us_per_call,derived")
    run()
