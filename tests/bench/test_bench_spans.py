"""The readers of the program's own spans, on hand-built span trees put in
the place of the tracer's record of finished roots; then a tiny traced run
on the CPU, whose program carries those spans."""

import shutil
import time

import pytest

from bench.harness import Window, run_cell
from bench.spec import ROOT, Benchmark
from bench_tiny import tiny_cell
from repro.engine import EngineConfig
from repro.engine.reduce import strip_bounds
from repro.obs import trace
from repro.obs.trace import Span

HOST = ("queue_wait_ms", "query_sketch_ms", "stage1_dispatch_ms",
        "stage1_collect_ms", "strips_per_batch")


def _sp(name, t0, t1, *children, **attrs):
    s = Span(name, None, attrs)
    s.t0, s.t1 = t0, t1
    s.children = list(children)
    return s


def _batch(t0, sketch, dispatch, collect, strips, requests, wait):
    """One flush as the program traces it, times in seconds from t0."""
    a = t0 + sketch
    b = a + dispatch
    return _sp("batcher.query", t0, b + collect + 0.01, _sp(
        "index.query", t0, b + collect,
        _sp("index.sketch", t0, a),
        _sp("index.fan.stage1", a, b + collect,
            _sp("index.fan.dispatch", a, b),
            _sp("index.fan.collect", b, b + collect), strips=strips)),
        requests=requests, queue_wait_ms=wait)


def _read(name, w):
    return Benchmark().metric_reader(name)(w)


def _recorded(monkeypatch, *roots):
    monkeypatch.setattr(trace, "recent_roots", lambda: list(roots))


def test_span_readers_average_over_the_flushed_batches(monkeypatch):
    _recorded(monkeypatch, _batch(0.0, 0.02, 5.0, 0.1, 1024, 2, 10.0),
              _batch(9.0, 0.04, 6.0, 0.3, 512, 6, 2.0),
              _sp("index.compact", 20.0, 21.0))
    w = Window()
    assert _read("queue_wait_ms", w) == pytest.approx((2 * 10 + 6 * 2) / 8)
    assert _read("query_sketch_ms", w) == pytest.approx(30.0)
    assert _read("stage1_dispatch_ms", w) == pytest.approx(5500.0)
    assert _read("stage1_collect_ms", w) == pytest.approx(200.0)
    assert _read("strips_per_batch", w) == pytest.approx(768.0)


def test_span_readers_read_nothing_from_a_program_without_the_spans(
        monkeypatch):
    # a flush span with rows, and no children
    _recorded(monkeypatch, _sp("batcher.query", 0.0, 5.0, rows=64))
    for name in HOST:
        assert _read(name, Window()) is None, name
    _recorded(monkeypatch)
    assert _read("queue_wait_ms", Window()) is None


@pytest.mark.parametrize("name", HOST)
def test_span_readers_read_nothing_from_a_program_that_keeps_no_roots(
        name, monkeypatch):
    # the parent program: its tracer keeps no record of finished roots
    monkeypatch.delattr(trace, "recent_roots")
    assert _read(name, Window()) is None


def test_a_tiny_traced_run_reports_the_span_metrics(tmp_path):
    # the benchmark's files alone under a root of this test's own: the
    # profiler writes under the root, and another test's traced run may be
    # writing under the repository's root from a parallel worker
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    bench = Benchmark(tmp_path)
    workload = "gist1m-p4.knn-plain"
    cell = tiny_cell(bench, workload)
    r = run_cell(bench, workload, seed=2**31 + 23, seconds=0.8, trace=True,
                 t_process=time.perf_counter(), require_chip=False, cell=cell,
                 log=lambda m: None)
    m = {k: v["value"] for k, v in r["metrics"].items()}
    assert set(HOST) <= set(m)
    # 3,000 rows in segments of 1,024: two sealed and the active one, each
    # 1,024 wide, in the CPU's strips
    _, _, col_block = EngineConfig().resolve()
    assert m["strips_per_batch"] == 3 * len(strip_bounds(1024, col_block))
    parts = m["query_sketch_ms"] + m["stage1_dispatch_ms"] + m[
        "stage1_collect_ms"]
    assert parts <= m["index_flush_ms"]
    assert m["queue_wait_ms"] >= 0.0
