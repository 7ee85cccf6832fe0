"""The control: the plain reference in the program's place, one precision
step below the configuration's (three bf16 passes for float32 at HIGHEST),
must fail the cell's committed limits, judged as a run is; the reference
against itself at HIGHEST reads nothing.  Tiny size, CPU;
``bench/calibrate.py`` reads the same numbers on the chip at the cell's own
size."""

import pytest

from bench.calibrate import control_readings, judged
from bench.spec import Benchmark
from bench_tiny import tiny_cell

CELLS = Benchmark().workload_names()


@pytest.mark.parametrize("name", CELLS)
def test_the_control_fails_the_limits(name):
    bench = Benchmark()
    cell = tiny_cell(bench, name)
    limits = cell.limits["limits"]
    for seed in (2**31 + 1, 7):
        got = control_readings(bench, cell, seed)
        over = {k: v for k, v in got.items() if v > limits[k]}
        assert over, (seed, got, limits)
        verdict = judged(cell, got)
        assert verdict["correct"] is False, verdict
        assert verdict["checks"]["failed"]["value"] == 0.0


@pytest.mark.parametrize("name", CELLS[:1])
def test_the_reference_against_itself_reads_zero(name):
    bench = Benchmark()
    cell = tiny_cell(bench, name)
    got = control_readings(bench, cell, 3, precision="highest")
    assert got == {"answer_gap": 0.0, "sketch_gap": 0.0}
