"""Readings that the limits of ``bench/limits/<workload>.json`` are set from.

    python3 bench/calibrate.py --workload <cell> --seeds 1 2 3 [--out file]

The control: the plain reference put in the program's place, computed one
precision step below the configuration's (``high``, three bf16 passes, for
float32 at ``highest``), on the cell's own corpus and query pool, at the
cell's own size, over as many answers as a window compares (the traffic's
``compare_max``, drawn from the seed).  Each seed prints one JSON line: the
numbers the control reads against the ``highest`` reference, judged by
``check.judge`` against the cell's limits as a run is, and ``correct``,
which the limits must make false.  The program's own readings are those of
the benchmark's runs (their ``checks``).
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def control_readings(bench, cell, seed: int, precision: str = "high") -> dict:
    """The numbers the control reads on one seed (no program runs)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from bench import check
    from bench.harness import make_queries, seeds

    cfg, traffic, sk = cell.config, cell.traffic, cell.config["sketch"]
    s = seeds(seed)
    gen = bench.module("data", cfg["data"]["generator"]).batch
    ref = bench.module("reference", cfg["reference"])
    key = jax.random.key(s["data"])
    params = {k: v for k, v in cfg["data"].items() if k != "generator"}
    batch = cfg["index"]["ingest_batch"]
    pool, src = make_queries(gen, key, cfg["rows"], batch, cfg["dim"], params,
                             traffic["query_pool"], traffic["query_noise"],
                             np.random.default_rng(s["queries"]))
    rng = np.random.default_rng(s["sample"])
    n = min(len(pool), traffic["compare_max"])
    pick = np.sort(rng.choice(len(pool), n, replace=False))
    Q = jnp.asarray(pool[pick])
    common = dict(gen=gen, data_key=key, index_seed=s["index"],
                  n_rows=cfg["rows"], batch_rows=batch, d=cfg["dim"],
                  gen_params=params, p=sk["p"], k=sk["k"],
                  block_d=sk["block_d"], estimator=traffic["estimator"],
                  top_k=traffic["top_k"])
    low_vals, low_ids, _, _ = ref.knn(
        Q, np.zeros((n, 1), np.int32), precision=precision, **common)
    ref_vals, ref_ids, at_served, norms = ref.knn(
        Q, low_ids, precision="highest", **common)
    out = check.answer_gap(low_vals, low_ids, ref_vals, ref_ids, at_served,
                            norms)
    R = ref.projection(s["index"], cfg["dim"], sk["k"], sk["block_d"])
    X = ref.rows_at(gen, key, np.unique(src[pick]), n=batch, d=cfg["dim"],
                    gen_params=params)
    low_U, _ = ref.sketch(X, R, p=sk["p"], precision=precision)
    ref_U, ref_M = ref.sketch(X, R, p=sk["p"], precision="highest")
    out.update(check.sketch_gap(low_U, ref_U, ref_M))
    return out


def judged(cell, numbers: dict) -> dict:
    """The control's numbers judged against the cell's limits as a run's
    are: its checks, and whether it would read ``correct``."""
    from bench import check

    verdict = check.judge(dict(numbers, failed=0.0), cell.limits["limits"])
    return {"correct": all(v["ok"] for v in verdict),
            "checks": {v["name"]: {"value": v["value"], "limit": v["limit"]}
                       for v in verdict}}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--out", type=Path)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT / "src"))
    sys.path.insert(0, str(ROOT))

    import jax

    from bench.harness import use_compile_cache
    from bench.spec import Benchmark

    if jax.devices()[0].platform != "tpu":
        print("calibrate: needs a TPU", file=sys.stderr)
        return 3
    use_compile_cache(ROOT)
    bench = Benchmark(ROOT)
    cell = bench.cell(args.workload)
    lines = []
    for seed in args.seeds:
        t0 = time.perf_counter()
        rd = control_readings(bench, cell, seed)
        line = {"workload": args.workload, "seed": seed, "control": rd,
                "seconds": time.perf_counter() - t0, **judged(cell, rd)}
        lines.append(line)
        print(json.dumps(line), flush=True)
    if args.out:
        args.out.parent.mkdir(parents=True, exist_ok=True)
        args.out.write_text("".join(json.dumps(x) + "\n" for x in lines))
    return 0


if __name__ == "__main__":
    sys.exit(main())
