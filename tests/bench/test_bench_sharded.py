"""The sharded deployment through the harness, on four CPU devices in a
process of its own (``sharded_runs.py``): the tiny cell is correct, the
stacked route serves every flush, memory is summed over the four devices,
the control, a misrouted id and the shards' exchange left out are all
judged not correct, and a run refuses a deployment other than configured.
Then, in this process, the readers the sharded cell adds, on hand-built
span trees and counter deltas."""

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

from bench.harness import Window
from bench.spec import Benchmark
from repro.obs import trace
from repro.obs.trace import Span

HERE = Path(__file__).resolve().parent


@pytest.fixture(scope="module")
def runs():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, str(HERE / "sharded_runs.py")],
                       env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode == 0, p.stderr[-4000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


def test_the_tiny_sharded_cell_is_correct(runs):
    for name in ("sound", "traced"):
        r = runs[name]
        assert r["correct"] is True, (name, r["checks"])
        assert r["failed"] == 0 and r["attempted"] > 0
        assert r["device"]["count"] == 4


def test_the_stacked_route_serves_every_flush(runs):
    m = runs["traced"]["metrics"]
    assert m["stacked_fan_share"]["value"] == 100.0
    assert m["shard_fan_ms"]["value"] > 0
    assert m["shard_rerank_ms"]["value"] > 0


def test_memory_is_summed_over_the_four_devices(runs):
    # the stand-in gives device i 1,000 * (i + 1) bytes
    r = runs["sound"]
    assert r["metrics"]["hbm_bytes_per_row"]["value"] == pytest.approx(
        (1000 + 2000 + 3000 + 4000) / runs["rows"])
    assert r["device"]["memory_peak_bytes"] == 4000


def test_the_control_is_judged_not_correct(runs):
    c = runs["control"]
    assert c["correct"] is False, c
    assert c["checks"]["failed"]["value"] == 0.0


@pytest.mark.parametrize("fault", ["misrouted", "exchange"])
def test_a_broken_sharded_path_is_judged_not_correct(runs, fault):
    r = runs[fault]
    assert r["correct"] is False
    gap = r["checks"]["answer_gap"]
    assert gap["value"] == "inf" or gap["value"] > gap["limit"]


def test_a_deployment_other_than_configured_is_refused(runs):
    assert "rows per shard [64, 64, 64, 0]" in runs["uneven"]
    assert "10 rows left in the active segment" in runs["unsealed"]


# ------------------------------------------------ readers, hand-built input

def _sp(name, t0, t1, *children, **attrs):
    s = Span(name, None, attrs)
    s.t0, s.t1 = t0, t1
    s.children = list(children)
    return s


def _flush(t0, fan, rerank, sharded=True):
    """One flush through a sharded index's stacked fan (or, with
    ``sharded=False``, the single-host fan's stage 1, which carries no
    ``shards``), times in seconds from t0."""
    attrs = {"shards": 4, "mode": "parallel"} if sharded else {"strips": 64}
    a = t0 + 0.001
    b = a + fan
    return _sp("batcher.query", t0, b + rerank + 0.001, _sp(
        "index.query", t0, b + rerank,
        _sp("index.sketch", t0, a),
        _sp("index.fan.stage1", a, b, **attrs),
        _sp("index.fan.stage2", b, b + rerank)),
        requests=64, queue_wait_ms=1.0)


def _read(name, w):
    return Benchmark().metric_reader(name)(w)


def test_shard_readers_average_over_the_flushed_batches(monkeypatch):
    monkeypatch.setattr(trace, "recent_roots", lambda: [
        _flush(0.0, 0.050, 0.002), _flush(1.0, 0.070, 0.004)])
    assert _read("shard_fan_ms", Window()) == pytest.approx(60.0)
    assert _read("shard_rerank_ms", Window()) == pytest.approx(3.0)


def test_shard_readers_read_nothing_without_a_sharded_fan(monkeypatch):
    # the single-host fan: stage 1 without ``shards``, and no stage 2
    single = _sp("batcher.query", 0.0, 0.1, _sp(
        "index.query", 0.0, 0.09,
        _sp("index.fan.stage1", 0.0, 0.08, strips=64)), requests=64)
    monkeypatch.setattr(trace, "recent_roots", lambda: [single])
    assert _read("shard_fan_ms", Window()) is None
    assert _read("shard_rerank_ms", Window()) is None
    monkeypatch.setattr(trace, "recent_roots", lambda: [])
    assert _read("shard_fan_ms", Window()) is None


@pytest.mark.parametrize("counters, share", [
    ({"index.stage1_parallel": 40, "index.stage1_dispatch": 0}, 100.0),
    ({"index.stage1_parallel": 30, "index.stage1_dispatch": 10}, 75.0),
    ({"index.stage1_dispatch": 8}, 0.0),
    ({"index.stage1_parallel": 0, "index.stage1_dispatch": 0}, None),
    ({"batcher.batches": 12}, None),
    ({}, None),
])
def test_stacked_fan_share_reads_the_window_counter_deltas(counters, share):
    got = _read("stacked_fan_share", Window(counters=counters))
    assert got == (pytest.approx(share) if share is not None else None)


def test_stacked_fan_share_reads_nothing_without_counters():
    assert _read("stacked_fan_share", Window()) is None
