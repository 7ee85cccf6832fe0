"""Run one benchmark cell and print its result as the last line of stdout.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration, traffic, metrics and limits are read from
``BENCHMARK.json`` and the files it names.  The program under test is the
checkout's ``src/repro``.  The run needs a TPU with as many chips as the
cell asks for; without them it exits non-zero and prints no result.  The
compared numbers and their limits are the last lines on stderr and the
``checks`` key of the result line.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    if not (ROOT / "src" / "repro").is_dir():
        print(f"bench: no program under test at {ROOT / 'src' / 'repro'}",
              file=sys.stderr)
        return 2
    here = str(Path(__file__).resolve().parent)
    sys.path[:] = [p for p in sys.path if p != here]
    sys.path.insert(0, str(ROOT / "src"))
    sys.path.insert(0, str(ROOT))

    from bench.harness import NoChip, run_cell, use_compile_cache
    from bench.spec import Benchmark

    use_compile_cache(ROOT)
    bench = Benchmark(ROOT)
    try:
        result = run_cell(bench, args.workload, seed=args.seed,
                          seconds=args.seconds, trace=bool(args.trace),
                          t_process=T_PROCESS)
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
