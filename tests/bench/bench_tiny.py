"""Tiny cells for running the harness on the CPU: the real configuration
files with their scale cut, so every code path runs in seconds."""

from __future__ import annotations

import copy
import dataclasses

from bench.spec import Benchmark


def tiny_cell(bench: Benchmark, workload: str):
    cell = bench.cell(workload)
    cfg = copy.deepcopy(cell.config)
    cfg.update(rows=3000, dim=32)
    cfg["sketch"].update(k=16, block_d=32)
    cfg["index"].update(segment_rows=1024, ingest_batch=512)
    cfg["front_door"].update(max_batch=8)
    tr = dict(cell.traffic, clients=8, query_pool=64, compare_max=64,
              batch_sizes=[8])
    return dataclasses.replace(cell, config=cfg, traffic=tr)


def tiny_sharded_cell(bench: Benchmark, workload: str):
    """A sharded cell cut to 4 shards x 2 segments of 64 rows, dim 16, and
    8 clients: run it on four devices (on the CPU,
    ``XLA_FLAGS=--xla_force_host_platform_device_count=4``)."""
    cell = bench.cell(workload)
    cfg = copy.deepcopy(cell.config)
    shards = cfg["index"]["shards"]
    cfg.update(rows=shards * 2 * 64, dim=16)
    cfg["sketch"].update(k=16, block_d=16)
    cfg["index"].update(segment_rows=64, ingest_batch=64)
    cfg["front_door"].update(max_batch=8)
    tr = dict(cell.traffic, clients=8, query_pool=64, compare_max=64,
              batch_sizes=[8])
    return dataclasses.replace(cell, config=cfg, traffic=tr)
