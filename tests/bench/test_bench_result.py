"""The result line's contract and the latency arithmetic, on the CPU.

The harness runs end to end here at a tiny size with the chip look
skipped (``require_chip=False``); ``bench/run.py`` itself must refuse the
CPU and a checkout without the program, printing no result.
"""

import json
import math
import os
import shutil
import subprocess
import sys
import time

import pytest

from bench import loop
from bench.harness import NoChip, run_cell
from bench.spec import ROOT, Benchmark
from bench_tiny import tiny_cell

CELL = "gist1m-p4.knn-plain"


def _req(lat, ok=True, rows=1):
    r = loop.Request(client=0, query=0, t_send=0.0)
    if ok:
        r.t_done, r.ids = lat, [[0] * 10] * rows
    else:
        r.error = "Overloaded"
    return r


def test_percentile_is_nearest_rank_and_failures_are_infinite():
    reqs = [_req(i / 1e3) for i in range(1, 101)]
    assert loop.percentile_ms(reqs, 95.0) == pytest.approx(95.0)
    assert loop.percentile_ms(reqs, 100.0) == pytest.approx(100.0)
    # six failures of 100 push the 95th percentile to infinity, five do not
    five = [_req(i / 1e3) for i in range(1, 96)] + [_req(0, ok=False)] * 5
    assert loop.percentile_ms(five, 95.0) == pytest.approx(95.0)
    six = [_req(i / 1e3) for i in range(1, 95)] + [_req(0, ok=False)] * 6
    assert math.isinf(loop.percentile_ms(six, 95.0))
    assert math.isnan(loop.percentile_ms([], 95.0))


def test_rows_per_s_spans_first_send_to_last_answer():
    a, b = _req(0.5), _req(2.0)
    b.t_send = 1.0
    assert loop.rows_per_s([a, b]) == pytest.approx(2 / 2.0)
    assert loop.rows_per_s([_req(0, ok=False)]) == 0.0


def test_client_orders_deal_one_permutation():
    import numpy as np

    orders = loop.client_orders(np.random.default_rng(3), 10, 4)
    got = np.concatenate(orders)
    assert sorted(got.tolist()) == list(range(10))
    assert [len(o) for o in orders] == [3, 3, 2, 2]


def test_the_loop_completes_the_round_open_at_the_close():
    """Four clients whose requests are served together, as a batcher that
    closes a batch when every caller is in it serves them, and who come
    back at staggered times: a close inside the stagger must not leave the
    last batch short of a caller."""
    import threading

    import numpy as np

    clients = 4
    barrier = threading.Barrier(clients, timeout=1.0)

    def send(row):
        i = barrier.wait()
        time.sleep(0.004 * i)
        return np.zeros((1, 10)), np.zeros((1, 10), np.int64)

    orders = loop.client_orders(np.random.default_rng(0), 16, clients)
    pool = np.zeros((16, 2), np.float32)
    for seconds in (0.05, 0.07, 0.09, 0.11):
        requests, *_ = loop.run_closed_loop(send, pool, orders, seconds,
                                            drain_s=5.0)
        assert all(r.ok for r in requests), [r.error for r in requests]
        counts = np.bincount([r.client for r in requests],
                             minlength=clients)
        assert len(set(counts.tolist())) == 1, counts


def test_the_loop_calls_at_from_its_own_thread_while_clients_run_on():
    import threading

    import numpy as np

    calls = []

    def send(row):
        time.sleep(0.002)
        return np.zeros((1, 10)), np.zeros((1, 10), np.int64)

    def at():
        calls.append((threading.current_thread(), time.perf_counter()))

    orders = loop.client_orders(np.random.default_rng(0), 8, 2)
    requests, t_open, t_close, _ = loop.run_closed_loop(
        send, np.zeros((8, 2), np.float32), orders, 0.3, at=(0.1, at))
    assert len(calls) == 1 and calls[0][0] is threading.current_thread()
    assert calls[0][1] - t_open == pytest.approx(0.1, abs=0.05)
    assert max(r.t_send for r in requests) > calls[0][1] + 0.1
    assert all(r.ok for r in requests)


@pytest.fixture(scope="module")
def lines():
    bench = Benchmark()
    cell = tiny_cell(bench, CELL)
    out = {}
    for trace in (False, True):
        out[trace] = run_cell(bench, CELL, seed=2**31 + 11, seconds=0.8,
                              trace=trace, t_process=time.perf_counter(),
                              require_chip=False, cell=cell,
                              log=lambda m: None)
    return out


def test_untraced_line_has_every_key_and_the_end_to_end_metrics(lines):
    r = lines[False]
    assert list(r)[:5] == ["correct", "attempted", "failed", "metrics",
                           "device"]
    assert list(r)[-1] == "checks"
    assert r["correct"] is True and r["failed"] == 0 and r["attempted"] > 0
    cell = Benchmark().cell(CELL)
    assert set(r["metrics"]) == {m.name for m in cell.end_to_end}
    for name, m in r["metrics"].items():
        assert m["value"] > 0 or name == "hbm_bytes_per_row"
    assert r["device"]["platform"] == "cpu" and r["device"]["count"] == 1
    for name, c in r["checks"].items():
        assert c["value"] <= c["limit"], name
    json.loads(json.dumps(r))


def test_traced_line_has_per_layer_metrics_and_the_trace_keys(lines):
    r = lines[True]
    assert r["correct"] is True
    cell = Benchmark().cell(CELL)
    allowed = {m.name for m in cell.per_layer}
    assert set(r["metrics"]) <= allowed
    # the CPU has no TPU planes: device metrics beyond idle read nothing,
    # the host-side ones are there
    assert {"batch_rows_mean", "index_flush_ms", "window_compiles"} <= set(
        r["metrics"])
    assert r["metrics"]["window_compiles"]["value"] == 0
    assert r["device"]["window_s"] > 0 and "busy_s" in r["device"]
    for part in ("device_ops", "idle_gaps"):
        assert len(r["breakdown"][part]) <= 10
    assert list(r)[-1] == "checks"


def test_the_harness_refuses_a_run_without_a_chip():
    bench = Benchmark()
    with pytest.raises(NoChip):
        run_cell(bench, CELL, seed=1, seconds=0.1, trace=False,
                 t_process=0.0, cell=tiny_cell(bench, CELL))


def _run(cwd, *args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", CELL, "--seed", "1",
         "--seconds", "1", "--trace", "0", *args],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_run_exits_nonzero_with_no_result_on_the_cpu():
    p = _run(ROOT)
    assert p.returncode != 0 and p.stdout.strip() == ""
    assert "needs a TPU" in p.stderr


def test_run_exits_nonzero_with_only_the_benchmark_files(tmp_path):
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    p = _run(tmp_path)
    assert p.returncode != 0 and p.stdout.strip() == ""
