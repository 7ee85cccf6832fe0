"""Find a cell's pieces by name.

``BENCHMARK.json`` at the checkout root names every cell (``workloads``),
configuration and metric.  Each piece lives in a file of its own, found by
name, so a new cell is new files plus a new entry and never an edit:

- a configuration is ``bench/configs/<config>.json`` (the path that
  ``BENCHMARK.json`` gives it);
- its rows come from ``bench/data/<generator>.py``, named in the
  configuration, and its plain reference from
  ``bench/reference/<reference>.py``;
- a traffic mix is ``bench/traffic/<traffic>.json``;
- a per-layer metric is ``bench/metrics/<metric>.py``, a ``read(window)``
  function that returns a number or None;
- the limits of a cell's comparison are ``bench/limits/<workload>.json``.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path
from types import ModuleType
from typing import List

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent


@dataclasses.dataclass(frozen=True)
class Metric:
    name: str
    unit: str


@dataclasses.dataclass(frozen=True)
class Cell:
    """One workload with everything it names, loaded."""

    name: str
    chips: int
    config: dict
    traffic: dict
    limits: dict
    end_to_end: List[Metric]
    per_layer: List[Metric]


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: Path) -> ModuleType:
    """Import one file of the benchmark by path, without a package import."""
    spec = importlib.util.spec_from_file_location(
        f"bench_{path.parent.name}_{path.stem}".replace("-", "_"), path)
    if spec is None or spec.loader is None:
        raise ImportError(f"cannot load {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _metric(entry: dict) -> Metric:
    return Metric(name=entry["name"], unit=entry["unit"])


def _reports(entry: dict, workload: str, e2e_names: List[str]) -> bool:
    """Does a metric entry belong to this workload?  A metric without a
    ``workloads`` list belongs to every cell (per-layer: every cell that
    reports the end-to-end metric it moves)."""
    cells = entry.get("workloads")
    if cells is not None:
        return workload in cells
    moves = entry.get("moves")
    return moves is None or moves in e2e_names


class Benchmark:
    """``BENCHMARK.json`` and the files it names, under one checkout."""

    def __init__(self, root: Path = ROOT):
        self.root = Path(root)
        self.bench_dir = self.root / "bench"
        self.doc = load_json(self.root / "BENCHMARK.json")

    def workload_names(self) -> List[str]:
        return [w["name"] for w in self.doc["workloads"]]

    def config(self, name: str) -> dict:
        for c in self.doc["configs"]:
            if c["name"] == name:
                cfg = load_json(self.root / c["file"])
                if cfg.get("name") != name:
                    raise ValueError(f"{c['file']} names {cfg.get('name')!r}, "
                                     f"not {name!r}")
                return cfg
        raise KeyError(f"no configuration {name!r} in BENCHMARK.json")

    def traffic(self, name: str) -> dict:
        return load_json(self.bench_dir / "traffic" / f"{name}.json")

    def limits(self, workload: str) -> dict:
        return load_json(self.bench_dir / "limits" / f"{workload}.json")

    def cell(self, workload: str) -> Cell:
        entry = next((w for w in self.doc["workloads"]
                      if w["name"] == workload), None)
        if entry is None:
            raise KeyError(f"no workload {workload!r} in BENCHMARK.json "
                           f"(have {self.workload_names()})")
        e2e = [_metric(m) for m in self.doc["end_to_end"]
               if _reports(m, workload, [])]
        names = [m.name for m in e2e]
        layer = [_metric(m) for m in self.doc["per_layer"]
                 if _reports(m, workload, names)]
        return Cell(name=workload, chips=int(entry["chips"]),
                    config=self.config(entry["config"]),
                    traffic=self.traffic(entry["traffic"]),
                    limits=self.limits(workload),
                    end_to_end=e2e, per_layer=layer)

    def metric_reader(self, name: str):
        """The ``read(window)`` function of a per-layer metric."""
        return load_module(self.bench_dir / "metrics" / f"{name}.py").read

    def module(self, kind: str, name: str) -> ModuleType:
        """A named data generator (``kind="data"``) or reference."""
        return load_module(self.bench_dir / kind / f"{name}.py")
