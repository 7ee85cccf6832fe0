"""``BENCHMARK.json`` against its contract, and the loader that finds every
piece of a cell by name."""

import hashlib
import json
import re
import shutil
from pathlib import Path

import pytest

from bench.spec import ROOT, Benchmark

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
DOC = json.loads((ROOT / "BENCHMARK.json").read_text())


def test_top_level_keys_and_command():
    assert set(DOC) == {"command", "paths", "run_seconds", "configs",
                        "workloads", "end_to_end", "per_layer"}
    assert 1 <= len(DOC["paths"]) <= 16
    for p in DOC["paths"]:
        assert re.fullmatch(r"[A-Za-z0-9_./\-]{1,200}", p) and ".." not in p
        assert (ROOT / p).is_dir()
    cmd = DOC["command"]
    assert 1 <= len(cmd) <= 32
    assert all(not w.startswith("/") and ".." not in w for w in cmd)
    assert cmd[1].startswith("bench/") and (ROOT / cmd[1]).is_file()


def test_run_seconds_fit_a_full_check_of_24_cells():
    rs = DOC["run_seconds"]
    assert isinstance(rs, int) and 1 <= rs <= 51
    runs = 2 + 14 * 24
    assert runs * (rs + 60) + 24 * 2 * 90 + 1200 <= 43200


def test_names_units_and_entry_keys():
    names = set()
    for c in DOC["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and len(c["reduced"]) <= 16
        assert all(NAME.match(k) for k in c["reduced"])
        assert c["file"].startswith("bench/configs/")
    for w in DOC["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and 1 <= len(w["why"]) <= 200
    for m in DOC["end_to_end"] + DOC["per_layer"]:
        assert NAME.match(m["name"]) and m["name"] not in names
        names.add(m["name"])
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for m in DOC["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    assert any(m["name"] == "setup_s" for m in DOC["end_to_end"])
    e2e = {m["name"] for m in DOC["end_to_end"]}
    for m in DOC["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["moves"] in e2e and "\n" not in m["layer"]
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
    four = sum(w["chips"] == 4 for w in DOC["workloads"])
    assert four <= max(1, len(DOC["workloads"]) // 2)


def test_every_workload_loads_with_its_readers_and_limits():
    bench = Benchmark()
    for name in bench.workload_names():
        cell = bench.cell(name)
        assert cell.per_layer and any(m.name == "setup_s"
                                      for m in cell.end_to_end)
        for m in cell.per_layer:
            assert callable(bench.metric_reader(m.name))
        assert cell.limits["limits"]["failed"] == 0
        bench.module("data", cell.config["data"]["generator"]).batch
        bench.module("reference", cell.config["reference"]).knn


def test_configuration_files_state_their_cut():
    for c in DOC["configs"]:
        cfg = json.loads((ROOT / c["file"]).read_text())
        assert cfg["name"] == c["name"] and cfg["source"] == c["source"]
        assert cfg["reduced"] == c["reduced"]
        for key in cfg["reduced"]:
            assert f"{key}_in_source" in cfg or key in cfg["assumed"]
        assert cfg["precision"] and cfg["assumed"]


def _digest(root: Path):
    return {p.relative_to(root): hashlib.sha256(p.read_bytes()).hexdigest()
            for p in root.rglob("*") if p.is_file()}


def test_a_new_cell_is_new_files_and_one_entry(tmp_path):
    """A configuration, a traffic mix and limits added as new files, plus a
    ``workloads`` entry, give a cell the loader finds; no existing file of
    the benchmark changes."""
    root = tmp_path / "checkout"
    shutil.copytree(ROOT / "bench", root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", root / "BENCHMARK.json")
    before = _digest(root / "bench")

    bench_dir = root / "bench"
    cfg = json.loads((bench_dir / "configs" / "gist1m-p4.json").read_text())
    cfg.update(name="sift1m-p6", dim=128, rows=1_000_000)
    cfg["sketch"].update(p=6, block_d=128)
    (bench_dir / "configs" / "sift1m-p6.json").write_text(json.dumps(cfg))
    mix = json.loads((bench_dir / "traffic" / "knn-plain.json").read_text())
    mix["clients"] = 32
    (bench_dir / "traffic" / "knn-plain-32.json").write_text(json.dumps(mix))
    (bench_dir / "limits" / "sift1m-p6.knn-plain-32.json").write_text(
        (bench_dir / "limits" / "gist1m-p4.knn-plain.json").read_text())
    doc = json.loads((root / "BENCHMARK.json").read_text())
    doc["configs"].append({"name": "sift1m-p6", "source": cfg["source"],
                           "file": "bench/configs/sift1m-p6.json",
                           "reduced": [], "why": "p = 6"})
    doc["workloads"].append({"name": "sift1m-p6.knn-plain-32",
                             "config": "sift1m-p6", "traffic": "knn-plain-32",
                             "chips": 1, "why": "32 clients"})
    (root / "BENCHMARK.json").write_text(json.dumps(doc))

    cell = Benchmark(root).cell("sift1m-p6.knn-plain-32")
    assert cell.config["sketch"]["p"] == 6
    assert cell.traffic["clients"] == 32
    after = _digest(bench_dir)
    assert {k: after[k] for k in before} == before
    assert len(after) == len(before) + 3


def test_an_unknown_workload_is_refused():
    with pytest.raises(KeyError, match="no workload"):
        Benchmark().cell("no-such-cell")
