"""Runs of the tiny sharded cell on four CPU devices, for
``test_bench_sharded.py``, which starts this file in a process of its own
(the device count is fixed when JAX starts):

    XLA_FLAGS=--xla_force_host_platform_device_count=4 JAX_PLATFORMS=cpu \
        python tests/bench/sharded_runs.py

Prints one JSON object as its last line: each run's result line (or the
control's verdict) by name.  ``sound`` and ``traced`` run the program as it
is; ``misrouted`` maps one id of every answer to the next row where stage 2
produces it; ``exchange`` leaves the candidates of shards 1-3 out of what
the shards hand to stage 2; ``control`` is ``bench/calibrate.py``'s reduced
precision judged against the cell's limits; ``uneven`` and ``unsealed``
are the error a run ends with when ingest leaves the shards unequal or
rows in the active segment.  Device memory is read through
a stand-in that gives device i 1,000 * (i + 1) bytes, which the CPU does
not count.
"""

from __future__ import annotations

import dataclasses
import json
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
for p in (ROOT / "src", ROOT, HERE):
    sys.path.insert(0, str(p))

CELL = "bigann-4m-p4-x4.knn-plain"
SEED = 2**31 + 13


def main() -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from bench import harness
    from bench.calibrate import control_readings, judged
    from bench.spec import Benchmark
    from bench_tiny import tiny_sharded_cell
    from repro.index import sharded

    if len(jax.devices()) != 4:
        raise SystemExit(f"needs 4 devices, found {len(jax.devices())}")
    harness.peak_bytes = lambda devices: [1000 * (d.id + 1) for d in devices]
    bench = Benchmark()
    cell = tiny_sharded_cell(bench, CELL)

    def run(trace=False, cell=cell):
        return harness.run_cell(bench, CELL, seed=SEED, seconds=1.0,
                                trace=trace, t_process=time.perf_counter(),
                                require_chip=False, cell=cell,
                                log=lambda m: None)

    def refused(rows):
        odd = dataclasses.replace(cell, config=dict(cell.config, rows=rows))
        try:
            run(cell=odd)
        except RuntimeError as e:
            return str(e)
        return None

    out = {"sound": run(), "traced": run(trace=True)}

    real_ids = sharded._ids_for_positions

    def misrouted(segments, pos):
        ids = real_ids(segments, pos)
        ids[:, 0] = (ids[:, 0] + 1) % cell.config["rows"]
        return ids

    sharded._ids_for_positions = misrouted
    try:
        out["misrouted"] = run()
    finally:
        sharded._ids_for_positions = real_ids

    real_fan = sharded.stacked_topk_shards

    def exchange_left_out(*a, **kw):
        vals, pos = real_fan(*a, **kw)
        return jnp.asarray(vals).at[1:].set(jnp.inf), pos

    sharded.stacked_topk_shards = exchange_left_out
    try:
        out["exchange"] = run()
    finally:
        sharded.stacked_topk_shards = real_fan

    out["uneven"] = refused(3 * 64)
    out["unsealed"] = refused(4 * 2 * 64 + 10)
    numbers = control_readings(bench, cell, SEED)
    out["control"] = dict(judged(cell, numbers), numbers=numbers)
    out["rows"] = cell.config["rows"]
    print(json.dumps(out, default=lambda x: np.asarray(x).tolist()))


if __name__ == "__main__":
    main()
