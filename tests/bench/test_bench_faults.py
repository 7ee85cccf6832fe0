"""A run with the timed path broken underneath must come out not correct.

Each fault is planted in the program, the harness then runs the rest of a
run as usual (tiny size, CPU, chip look skipped), and ``correct`` must read
false: an answer altered where it is produced, a batch whose answers go to
the wrong callers, and an ingest that stores a lower-precision sketch.
"""

import time

import jax.numpy as jnp
import numpy as np

from bench.harness import run_cell
from bench.spec import Benchmark
from bench_tiny import tiny_cell

CELL = "gist1m-p4.knn-plain"


def _run(cell_name=CELL):
    bench = Benchmark()
    cell = tiny_cell(bench, cell_name)
    return run_cell(bench, cell_name, seed=2**31 + 5, seconds=0.6,
                    trace=False, t_process=time.perf_counter(),
                    require_chip=False, cell=cell, log=lambda m: None)


def _failed(result):
    return {n for n, c in result["checks"].items()
            if not (isinstance(c["value"], float) and c["value"] <= c["limit"])}


def test_sound_run_is_correct():
    assert _run()["correct"] is True


def test_an_answer_altered_where_it_is_produced(monkeypatch):
    from repro.index import service

    real = service.fan_topk

    def altered(*a, **kw):
        vals, ids = real(*a, **kw)
        ids = np.array(ids)
        ids[:, 1] = (ids[:, 1] + 1) % 3000
        return vals, ids

    monkeypatch.setattr(service, "fan_topk", altered)
    r = _run()
    assert r["correct"] is False and "answer_gap" in _failed(r)


def test_answers_routed_to_the_wrong_callers(monkeypatch):
    from repro.index.query import MicroBatcher

    real = MicroBatcher._run

    def rolled(self, batch, key):
        real(self, batch, key)
        d, i = batch.results
        batch.results = (jnp.roll(d, 1, axis=0), np.roll(i, 1, axis=0))

    monkeypatch.setattr(MicroBatcher, "_run", rolled)
    # the configured window fills batches, so a roll moves answers
    r = _run()
    assert r["correct"] is False
    assert "answer_gap" in _failed(r)


def test_an_ingest_that_stores_a_rounded_sketch(monkeypatch):
    from repro.core.sketch import LpSketch
    from repro.index import service

    real = service.sketch

    def rounded(X, key, cfg):
        sk = real(X, key, cfg)
        if X.shape[0] > 64:  # corpus batches; queries stay exact
            sk = LpSketch(U=sk.U.astype(jnp.bfloat16).astype(jnp.float32),
                          moments=sk.moments)
        return sk

    monkeypatch.setattr(service, "sketch", rounded)
    r = _run()
    assert r["correct"] is False and "sketch_gap" in _failed(r)
