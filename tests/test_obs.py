"""Observability layer invariants: histogram bucket/percentile math under a
deterministic clock, span nesting + trace-id propagation, spans as profiler
host events, the zero-allocation disabled path, the single-host query's span
tree and its strip and queue-wait attributes, slow-log admission order, and
the MicroBatcher's atomic serving counters under concurrent flushes.
"""

import threading
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.core import SketchConfig
from repro.engine import EngineConfig
from repro.engine.reduce import strip_bounds
from repro.index import IndexConfig, SketchIndex
from repro.index.query import MicroBatcher, _segment_rows
from repro.obs.metrics import Counter, Histogram, MetricsRegistry
from repro.obs.slowlog import SlowQueryLog

CFG = SketchConfig(p=4, k=32, block_d=64)


@pytest.fixture(autouse=True)
def _obs_clean():
    """Every test starts from the disabled default and leaves no sinks."""
    obs.disable()
    obs.GLOBAL_SLOW_LOG.clear()
    yield
    obs.disable()
    obs.GLOBAL_SLOW_LOG.clear()


# --------------------------------------------------------------- histograms


def test_histogram_bucket_placement_and_totals():
    h = Histogram("t", buckets=(1.0, 2.0, 4.0, 8.0))
    for v in (0.5, 1.5, 3.0, 10.0):
        h.observe(v)
    s = h.summary()
    assert s["count"] == 4
    assert s["sum"] == pytest.approx(15.0)
    assert s["mean"] == pytest.approx(3.75)
    # cumulative prometheus buckets: le=1 -> 1, le=2 -> 2, le=4 -> 3,
    # le=8 -> 3, +inf -> 4
    cum = h.cumulative()
    assert [c for _le, c in cum] == [1, 2, 3, 3, 4]


def test_histogram_percentiles_deterministic():
    h = Histogram("t", buckets=(1.0, 2.0, 4.0, 8.0))
    for v in (0.5, 1.5, 3.0, 10.0):
        h.observe(v)
    # rank(p50) = ceil(0.5*4) = 2 -> the (1, 2] bucket's upper edge
    assert h.percentile(50) == pytest.approx(2.0)
    # p99 -> +inf bucket -> clamps to the observed max, never infinity
    assert h.percentile(99) == pytest.approx(10.0)
    assert np.isfinite(h.percentile(100))
    # single observation: every percentile is that observation
    h2 = Histogram("t2", buckets=(1.0, 100.0))
    h2.observe(7.0)
    for p in (1, 50, 95, 99):
        assert h2.percentile(p) == pytest.approx(7.0)


def test_histogram_percentile_clamps_to_observed_range():
    # all mass in one wide bucket: interpolation must not wander outside
    # what was actually observed
    h = Histogram("t", buckets=(1000.0,))
    for v in (5.0, 6.0, 7.0):
        h.observe(v)
    assert 5.0 <= h.percentile(50) <= 7.0
    assert 5.0 <= h.percentile(99) <= 7.0


def test_histogram_empty_summary():
    s = Histogram("t").summary()
    assert s["count"] == 0
    assert s["p50"] == 0.0 and s["p99"] == 0.0


def test_counter_concurrent_incs_lose_nothing():
    c = Counter("c")
    n_threads, per = 8, 2000

    def work():
        for _ in range(per):
            c.inc()

    ts = [threading.Thread(target=work) for _ in range(n_threads)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert c.value == n_threads * per


def test_registry_get_or_create_and_type_guard():
    reg = MetricsRegistry()
    c = reg.counter("a.b", "help")
    assert reg.counter("a.b") is c
    with pytest.raises(TypeError):
        reg.histogram("a.b")
    g = reg.gauge("g")
    g.set(3.5)
    snap = reg.snapshot()
    assert snap["a.b"] == 0 and snap["g"] == 3.5


def test_prometheus_exposition_shapes():
    reg = MetricsRegistry()
    reg.counter("index.queries", "total queries").inc(3)
    reg.gauge("index.live_rows").set(42)
    reg.histogram("q.ms", buckets=(1.0, 10.0)).observe(5.0)
    text = reg.prometheus()
    assert "index_queries_total 3" in text
    assert "index_live_rows 42" in text
    assert 'q_ms_bucket{le="1"} 0' in text
    assert 'q_ms_bucket{le="10"} 1' in text
    assert 'q_ms_bucket{le="+Inf"} 1' in text
    assert "q_ms_count 1" in text


# -------------------------------------------------------------------- spans


def test_span_nesting_and_trace_id_propagation():
    obs.enable()
    roots = []
    obs.trace.add_sink(roots.append)
    try:
        with obs.span("a", x=1) as a:
            with obs.span("b"):
                with obs.span("c") as c:
                    assert c.trace_id == a.trace_id
                    assert obs.trace.current_trace_id() == a.trace_id
            with obs.span("d"):
                pass
        with obs.span("e") as e:
            pass
    finally:
        obs.trace.remove_sink(roots.append)
    assert [r.name for r in roots] == ["a", "e"]
    assert e.trace_id == a.trace_id + 1  # fresh root, fresh trace
    tree = roots[0].to_dict()
    assert [ch["name"] for ch in tree["children"]] == ["b", "d"]
    assert tree["children"][0]["children"][0]["name"] == "c"
    assert all(ch["trace_id"] == a.trace_id
               for ch in tree["children"])
    assert tree["attrs"] == {"x": 1}
    assert tree["duration_ms"] >= sum(
        ch["duration_ms"] for ch in tree["children"]) - 1e-6


def test_recent_roots_are_the_roots_finished_since_the_last_enable():
    obs.enable()
    with obs.span("a"):
        with obs.span("b"):
            pass
    with obs.span("c"):
        pass
    obs.disable()
    with obs.span("off"):
        pass
    # kept after disable(), children only inside their roots
    assert [r.name for r in obs.trace.recent_roots()] == ["a", "c"]
    assert [ch.name for ch in obs.trace.recent_roots()[0].children] == ["b"]
    obs.enable()
    assert obs.trace.recent_roots() == []


def test_recent_roots_keep_the_newest():
    obs.enable()
    extra = 3
    for i in range(obs.trace.RECENT_ROOTS + extra):
        with obs.span("r", i=i):
            pass
    kept = obs.trace.recent_roots()
    assert len(kept) == obs.trace.RECENT_ROOTS
    assert [kept[0].attrs["i"], kept[-1].attrs["i"]] == [
        extra, obs.trace.RECENT_ROOTS + extra - 1]


def test_recent_roots_hold_the_roots_of_every_thread():
    obs.enable()

    def work(i):
        with obs.span("root", i=i):
            with obs.span("child"):
                pass

    ts = [threading.Thread(target=work, args=(i,)) for i in range(8)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    roots = obs.trace.recent_roots()
    assert sorted(r.attrs["i"] for r in roots) == list(range(8))
    assert all([ch.name for ch in r.children] == ["child"] for r in roots)


def test_span_metric_feeds_histogram():
    obs.enable()
    with obs.span("timed", metric="timed.ms"):
        pass
    s = obs.REGISTRY.histogram("timed.ms").summary()
    assert s["count"] == 1 and s["sum"] >= 0.0


def test_trace_ids_distinct_across_threads():
    obs.enable()
    ids = []
    lock = threading.Lock()

    def work():
        with obs.span("root") as sp:
            with lock:
                ids.append(sp.trace_id)

    ts = [threading.Thread(target=work) for _ in range(8)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert len(set(ids)) == 8  # each thread's root is its own trace


def test_disabled_span_is_shared_noop_singleton():
    assert not obs.enabled()
    sp = obs.span("anything", big_attr=list(range(100)))
    assert sp is obs.NULL_SPAN
    assert sp is obs.span("other")  # one shared object, no per-call alloc
    assert not sp  # falsy: `if sp:` guards skip attr work entirely
    with sp as inner:
        inner.set(x=1)  # no-op, never raises
    assert obs.trace.current_trace_id() == 0


def test_disabled_spans_record_nothing():
    before = obs.REGISTRY.histogram("off.ms").summary()["count"]
    with obs.span("index.query", metric="off.ms"):
        pass
    assert obs.REGISTRY.histogram("off.ms").summary()["count"] == before
    assert len(obs.GLOBAL_SLOW_LOG) == 0


# ------------------------------------------------- spans in a profiler trace


def _profiled(tmp_path, fn):
    """Run ``fn`` under ``jax.profiler`` and return the host plane's lines
    as lists of (name, start_ns, end_ns), one list per trace line."""
    from jax.profiler import ProfileData

    jax.profiler.start_trace(str(tmp_path))
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    (path,) = Path(tmp_path).glob("plugins/profile/*/*.xplane.pb")
    lines = []
    for plane in ProfileData.from_file(str(path)).planes:
        if plane.name.startswith("/host:"):
            lines.extend([(e.name, e.start_ns, e.start_ns + e.duration_ns)
                          for e in line.events] for line in plane.lines)
    return lines


def test_enabled_spans_are_profiler_host_events_on_their_own_thread(tmp_path):
    def elsewhere():
        with obs.span("t.other"):
            pass

    def work():
        obs.enable()
        with obs.span("t.outer", rows=3):
            with obs.span("t.inner"):
                jnp.ones(4).block_until_ready()
        other = threading.Thread(target=elsewhere)
        other.start()
        other.join(timeout=30)
        assert not other.is_alive()

    lines = _profiled(tmp_path, work)
    ours = [[e for e in line if e[0].startswith("t.")] for line in lines]
    ours = [line for line in ours if line]
    assert sorted(len(line) for line in ours) == [1, 2]
    main = next(line for line in ours if len(line) == 2)
    (other,) = next(line for line in ours if len(line) == 1)
    (outer,) = [e for e in main if e[0] == "t.outer"]
    (inner,) = [e for e in main if e[0] == "t.inner"]
    # the name alone, nested in the parent's interval, on the parent's line
    assert outer[1] <= inner[1] <= inner[2] <= outer[2]
    assert other[0] == "t.other"


def test_disabled_spans_emit_no_profiler_event(tmp_path):
    def work():
        assert not obs.enabled()
        with obs.span("t.off"):
            with obs.span("t.off.child"):
                jnp.ones(4).block_until_ready()

    names = {e[0] for line in _profiled(tmp_path, work) for e in line}
    assert not {"t.off", "t.off.child"} & names


def test_single_host_span_tree_through_the_batcher(rng):
    obs.enable()
    roots = []
    obs.trace.add_sink(roots.append)
    X = rng.uniform(0, 1, (150, 128)).astype(np.float32)
    idx = SketchIndex(CFG, seed=5, index_cfg=IndexConfig(segment_capacity=64),
                      engine=EngineConfig(col_block=16))
    idx.ingest(jnp.asarray(X))
    try:
        MicroBatcher(idx, max_batch=2, max_wait_ms=1.0).query(
            jnp.asarray(X[:2]), top_k=3)
    finally:
        obs.trace.remove_sink(roots.append)

    def shape(sp):
        return (sp.name, [shape(c) for c in sp.children])

    (root,) = roots
    assert shape(root) == (
        "batcher.query",
        [("index.query",
          [("index.sketch", []),
           ("index.fan.stage1", [("index.fan.dispatch", []),
                                 ("index.fan.collect", [])])])])
    (stage1,) = root.find("index.fan.stage1")
    _, _, col_block = idx.engine.resolve()
    segments = idx._segments()
    assert len(segments) == 3
    assert stage1.attrs["strips"] == sum(
        len(strip_bounds(_segment_rows(s), col_block)) for s in segments)
    assert root.attrs["requests"] == 1 and root.attrs["queue_wait_ms"] >= 0.0


def test_queue_wait_is_the_mean_over_every_request_of_the_batch(rng):
    obs.enable()
    X = rng.uniform(0, 1, (8, 128)).astype(np.float32)
    idx = SketchIndex(CFG, seed=5)
    idx.ingest(jnp.asarray(X))
    mb = MicroBatcher(idx, max_batch=3, max_wait_ms=60_000.0)
    roots = []
    fake = [1.0]
    real = obs.trace.clock
    obs.trace.clock = lambda: fake[0]
    obs.trace.add_sink(roots.append)
    waiters = []
    try:
        # two callers join at t=1 and t=2; the third, at t=4, fills the
        # batch and flushes it at once
        for t in (1.0, 2.0):
            fake[0] = t
            th = threading.Thread(target=mb.query, args=(jnp.asarray(X[:1]),),
                                  kwargs={"top_k": 3})
            th.start()
            waiters.append(th)
            while mb.stats()["queue_depth"] < len(waiters):
                threading.Event().wait(0.001)
        fake[0] = 4.0
        mb.query(jnp.asarray(X[:1]), top_k=3)
        for th in waiters:
            th.join(timeout=60)
            assert not th.is_alive()
    finally:
        obs.trace.clock = real
        obs.trace.remove_sink(roots.append)
    (root,) = [r for r in roots if r.name == "batcher.query"]
    assert root.attrs["requests"] == 3
    # ((4 - 1) + (4 - 2) + (4 - 4)) / 3 seconds
    assert root.attrs["queue_wait_ms"] == pytest.approx(5000.0 / 3)


# ----------------------------------------------------------------- slow log


def test_slowlog_keeps_worst_n_in_order():
    obs.enable()
    fake = [0.0]
    real = obs.trace.clock
    obs.trace.clock = lambda: fake[0]
    try:
        log = SlowQueryLog(capacity=3)
        for i, dur in enumerate([5.0, 1.0, 9.0, 3.0, 7.0]):
            with obs.span("index.query", i=i) as sp:
                fake[0] += dur
            log.offer(sp)
    finally:
        obs.trace.clock = real
    # every offer beat the then-floor, so all were admitted (two were later
    # evicted by slower traces); only the 3 worst remain
    assert log.offered == 5 and log.admitted == 5
    got = [(e["attrs"]["i"], e["duration_ms"]) for e in log.entries()]
    assert got == [(2, pytest.approx(9000.0)), (4, pytest.approx(7000.0)),
                   (0, pytest.approx(5000.0))]
    assert "index.query" in log.dump()


def test_slowlog_filters_non_query_roots():
    obs.enable()
    log = SlowQueryLog(capacity=4)
    with obs.span("index.compact") as sp:
        pass
    assert log.offer(sp) is False
    assert len(log) == 0


# ------------------------------------------------- batcher serving counters


def test_microbatcher_stats_counters_exact_under_concurrent_flushes(rng):
    X = rng.uniform(0, 1, (64, 128)).astype(np.float32)
    idx = SketchIndex(SketchConfig(p=4, k=16, block_d=64), seed=5,
                      index_cfg=IndexConfig(segment_capacity=64))
    idx.ingest(jnp.asarray(X))
    # max_batch=1: every request claims its own flush, so many _run() calls
    # finish concurrently — exactly the interleaving that loses counts if
    # the counters were read-modify-written without atomicity
    mb = MicroBatcher(idx, max_batch=1, max_wait_ms=0.1)
    n_threads, per = 8, 12
    errs = []

    def work():
        q = jnp.asarray(X[:1])
        try:
            for _ in range(per):
                mb.query(q, top_k=3)
        except Exception as e:  # pragma: no cover - failure detail
            errs.append(e)

    ts = [threading.Thread(target=work) for _ in range(n_threads)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    mb.flush()
    assert not errs
    st = mb.stats()
    assert st["rows_served"] == n_threads * per
    assert st["batches_run"] == n_threads * per  # 1-row batches, none lost
    assert st["open_groups"] == 0


def test_microbatcher_stats_histograms_fill_when_enabled(rng):
    obs.enable()
    X = rng.uniform(0, 1, (8, 128)).astype(np.float32)
    idx = SketchIndex(SketchConfig(p=4, k=16, block_d=64), seed=5)
    idx.ingest(jnp.asarray(X))
    mb = MicroBatcher(idx, max_batch=4, max_wait_ms=0.5)
    mb.query(jnp.asarray(X[:2]), top_k=3)
    st = mb.stats()
    assert st["batch_rows"]["count"] >= 1
    assert st["flush_ms"]["count"] >= 1
    assert st["queue_wait_ms"]["count"] >= 1
    assert st["flush_ms"]["p95"] >= 0.0


# -------------------------------------------------- index stats() exposure


def test_index_stats_exposes_latency_and_slow_queries(rng):
    obs.enable()
    X = rng.uniform(0, 1, (40, 128)).astype(np.float32)
    idx = SketchIndex(CFG, seed=5, index_cfg=IndexConfig(segment_capacity=16))
    idx.ingest(jnp.asarray(X))
    idx.query(jnp.asarray(X[:2]), top_k=3)
    idx.query_threshold(jnp.asarray(X[:2]), radius=0.5)
    idx.compact()
    st = idx.stats()
    lat = st["latency"]
    assert lat["query_ms"]["count"] >= 1
    assert lat["threshold_ms"]["count"] >= 1
    assert lat["compact_ms"]["count"] >= 1
    for k in ("p50", "p95", "p99"):
        assert lat["query_ms"][k] >= 0.0
    slow = st["slow_queries"]
    assert slow and slow[0]["name"] == "index.query"
    assert {e["name"] for e in slow} <= {"index.query", "batcher.query"}
