"""One run of one cell: set-up, the measured window, the read-outs, the
comparison with the plain reference, and the result line.

``run_cell`` is what ``bench/run.py`` calls.  Tests call it too, on the CPU
at a tiny size, with ``require_chip=False``; nothing else differs.
"""

from __future__ import annotations

import gc
import math
import shutil
import sys
import tempfile
import threading
import time
from pathlib import Path
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from . import check, loop, roofline, tracing
from .spec import Benchmark, Cell

CACHE_DIR = ".jax_cache"
TRACE_DIR = ".bench_trace"


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


class CompileWatch:
    """Backend compiles and persistent-cache loads, with their times, from
    JAX's own monitoring events (registered once per process)."""

    _instance: Optional["CompileWatch"] = None

    def __init__(self):
        self.events: List[float] = []

    @classmethod
    def get(cls) -> "CompileWatch":
        if cls._instance is None:
            cls._instance = cls()
            jax.monitoring.register_event_duration_secs_listener(
                cls._instance._duration)
            jax.monitoring.register_event_listener(cls._instance._event)
        return cls._instance

    def _duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.events.append(time.perf_counter())

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.events.append(time.perf_counter())

    def between(self, t0: float, t1: float) -> int:
        return sum(1 for t in self.events if t0 <= t < t1)


def use_compile_cache(root: Path) -> str:
    """JAX's persistent cache at a fixed place inside the checkout."""
    path = str(Path(root) / CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


def chips_for(cell: Cell, require_chip: bool) -> list:
    devices = jax.devices()
    if require_chip:
        if devices[0].platform != "tpu":
            raise NoChip(f"needs a TPU; JAX found {devices[0].platform!r}")
        if len(devices) < cell.chips:
            raise NoChip(f"cell asks for {cell.chips} chips; JAX found "
                         f"{len(devices)}")
    if len(devices) < cell.chips:
        raise NoChip(f"cell asks for {cell.chips} devices; found "
                     f"{len(devices)}")
    return devices[:cell.chips]


def peak_bytes(devices) -> list:
    """Each device's ``peak_bytes_in_use`` (None where it keeps no count)."""
    return [(d.memory_stats() or {}).get("peak_bytes_in_use")
            for d in devices]


def seeds(seed: int) -> Dict[str, int]:
    """Independent 32-bit streams from one seed of any size."""
    words = np.random.SeedSequence(int(seed)).generate_state(4)
    return {"index": int(words[0]), "data": int(words[1]),
            "queries": int(words[2]), "sample": int(words[3])}


def make_queries(gen, key, rows: int, batch_rows: int, dim: int,
                 gen_params: dict, pool: int, noise: float, rng):
    """(pool (P, d) host float32, source ids (P,)): corpus rows drawn
    uniformly, perturbed as max(x + noise * N(0, 1), 0)."""
    from .reference.lp_sketch import rows_at

    src = rng.choice(rows, size=pool, replace=pool > rows)
    X = rows_at(gen, key, src, n=batch_rows, d=dim, gen_params=gen_params)
    eps = jax.random.normal(jax.random.fold_in(key, 1 << 30), X.shape)
    return np.asarray(jnp.maximum(X + noise * eps, 0.0)), src


def counter_values() -> Dict[str, int]:
    """Every counter of the program's metrics registry, by name."""
    from repro.obs.metrics import REGISTRY, Counter

    out = {}
    for name in REGISTRY.names():
        m = REGISTRY.get(name)
        if isinstance(m, Counter):
            out[name] = m.value
    return out


class Window:
    """What the per-layer readers see of one window (``bench/metrics``):
    among others ``counters``, the window's change of every counter of the
    program's registry (name -> delta)."""

    def __init__(self, **kw):
        self.__dict__.update(kw)


class _WindowMarker:
    """Marks the traced window with one host event, entered and left on a
    thread of its own; ``stop`` (idempotent) ends the event, disables the
    program's tracing and stops the profiler.  Call it from the thread that
    started the profiler: stopped from another thread, the profiler took
    about three times as long per event kept (PERF.md).  ``bounds`` is the
    traced part on the span clock, ``stop_s`` the seconds the profiler took
    to stop."""

    def __init__(self):
        from repro.obs import trace as obs_trace

        self._clock = obs_trace.clock
        self._stop = threading.Event()
        self._lock = threading.Lock()
        self._entered = threading.Event()
        self._thread = threading.Thread(target=self._hold, daemon=True)
        self.bounds = None
        self.stop_s = None
        self._t0 = None
        self._thread.start()
        self._entered.wait()

    def _hold(self):
        with jax.profiler.TraceAnnotation(tracing.WINDOW_EVENT):
            self._t0 = self._clock()
            self._entered.set()
            self._stop.wait()

    def stop(self):
        from repro import obs

        with self._lock:
            if self.bounds is not None:
                return
            self._stop.set()
            self._thread.join()
            self.bounds = (self._t0, self._clock())
            obs.disable()
            jax.profiler.stop_trace()
            self.stop_s = self._clock() - self.bounds[1]


def _finite(v):
    return v if v is None or math.isfinite(v) else str(v)


def _batch_weights(spans, lo: float, hi: float):
    """[(rows, share of the batch's flush inside [lo, hi])] on one clock."""
    out = []
    for t0, t1, rows in spans:
        dur = max(t1 - t0, 1e-12)
        inside = max(0.0, min(t1, hi) - max(t0, lo))
        if inside > 0:
            out.append((rows, min(1.0, inside / dur)))
    return out


def run_cell(bench: Benchmark, workload: str, *, seed: int, seconds: float,
             trace: bool, t_process: float, require_chip: bool = True,
             cell: Optional[Cell] = None, log=None) -> dict:
    """Run one cell and return its result line as a dict."""
    from repro import obs
    from repro.obs.metrics import REGISTRY

    from .system import System

    out = log or (lambda msg: print(msg, file=sys.stderr, flush=True))

    def log(msg):
        out(f"[{time.perf_counter() - t_process:7.1f} s] {msg}")

    cell = cell or bench.cell(workload)
    devices = chips_for(cell, require_chip)
    watch = CompileWatch.get()
    cfg, traffic = cell.config, cell.traffic
    s = seeds(seed)
    gen = bench.module("data", cfg["data"]["generator"]).batch
    ref = bench.module("reference", cfg["reference"])
    system = System(cfg, traffic, gen, index_seed=s["index"],
                    data_key=jax.random.key(s["data"]), devices=devices)
    log(f"cell {workload}: {cfg['rows']} rows x {cfg['dim']}, "
        f"{len(devices)} x {devices[0].device_kind}, seed {seed}")

    # ---------------------------------------------------------- set-up
    pool, src = make_queries(gen, system.data_key, system.rows,
                             system.batch_rows, system.dim, system.gen_params,
                             traffic["query_pool"], traffic["query_noise"],
                             np.random.default_rng(s["queries"]))
    log(f"query pool of {len(pool)} rows made")
    system.warm_ingest()
    log("ingest programs warmed")
    ingest_s = system.ingest()
    log(f"ingest {system.rows} rows in {ingest_s:.3f} s")
    fd = system.serve()
    system.warm_batches(pool)
    log(f"batch sizes {traffic['batch_sizes']} warmed")
    orders = loop.client_orders(np.random.default_rng(s["queries"] + 1),
                                len(pool), traffic["clients"])
    spans: list = []

    def sink(root):
        for sp in root.find("batcher.query"):
            spans.append((sp.t0, sp.t1, int(sp.attrs.get("rows", 0))))

    rows_c = REGISTRY.counter("batcher.rows")
    batches_c = REGISTRY.counter("batcher.batches")
    if trace:
        # a directory of this run's own: runs in one checkout may overlap
        (Path(bench.root) / TRACE_DIR).mkdir(exist_ok=True)
        trace_dir = Path(tempfile.mkdtemp(prefix="run-",
                                          dir=Path(bench.root) / TRACE_DIR))
        obs.enable()
        obs.trace.add_sink(sink)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    rows0, batches0 = rows_c.value, batches_c.value
    counters0 = counter_values()
    setup_s = time.perf_counter() - t_process

    # ---------------------------------------------------------- window
    send = (lambda row: fd.query(row, top_k=traffic["top_k"],
                                 estimator=traffic["estimator"]))
    marker = _WindowMarker() if trace else None
    cut = cfg.get("trace_window_s")
    t_win = time.perf_counter()
    requests, t_open, t_close, t_drained = loop.run_closed_loop(
        send, pool, orders, seconds, annotate=trace,
        at=(cut, marker.stop) if trace and cut is not None else None)
    if trace:
        t_loop = time.perf_counter()
        marker.stop()
        log(f"traced part {marker.bounds[1] - marker.bounds[0]:.3f} s; the "
            f"profiler stopped in {marker.stop_s:.3f} s, "
            f"{time.perf_counter() - t_loop:.3f} s of it after the loop")
    window_compiles = watch.between(t_win, t_drained)
    rows_d, batches_d = rows_c.value - rows0, batches_c.value - batches0
    counters = {n: v - counters0.get(n, 0)
                for n, v in counter_values().items()}
    if trace:
        obs.trace.remove_sink(sink)

    # ------------------------------------------------------- read-outs
    mem = peak_bytes(devices)
    if any(m is None for m in mem):
        if require_chip:
            raise RuntimeError("the device reports no peak_bytes_in_use")
        mem = [0 for _ in mem]  # the CPU keeps no such count
    failed = sum(1 for r in requests if not r.ok)
    answered = [r for r in requests if r.ok]
    rng = np.random.default_rng(s["sample"])
    cmp_n = min(len(answered), traffic["compare_max"])
    sample = ([answered[i] for i in
               np.sort(rng.choice(len(answered), cmp_n, replace=False))]
              if cmp_n else [])
    sources = np.unique(src[[r.query for r in sample]])
    stored = system.stored_sketch(sources)
    live_rows = system.index.n_live
    fd = send = None
    system.close()
    gc.collect()

    e2e = {
        "setup_s": setup_s,
        "knn_rows_per_s": loop.rows_per_s(requests),
        "knn_p95_ms": loop.percentile_ms(requests, 95.0),
        "ingest_rows_per_s": system.rows / ingest_s,
        "hbm_bytes_per_row": float(sum(mem)) / live_rows,
    }
    log(f"window: {len(requests)} requests, {failed} failed, "
        f"{batches_d} batches, {rows_d} rows, compiles {window_compiles}")
    log("window counters: " + ", ".join(
        f"{n} {v}" for n, v in sorted(counters.items()) if v))

    # ------------------------------------------------------ comparison
    sk = cfg["sketch"]
    common = dict(gen=gen, data_key=system.data_key, index_seed=s["index"],
                  n_rows=system.rows, batch_rows=system.batch_rows,
                  d=system.dim, gen_params=system.gen_params, p=sk["p"],
                  k=sk["k"], block_d=sk["block_d"],
                  estimator=traffic["estimator"], precision="highest",
                  top_k=traffic["top_k"])
    numbers = {"failed": float(failed)}
    if sample:
        ref_vals, ref_ids, at_served, norms = ref.knn(
            jnp.asarray(pool[[r.query for r in sample]]),
            np.concatenate([r.ids for r in sample]), **common)
        numbers.update(check.answer_gap(
            np.concatenate([r.values for r in sample]),
            np.concatenate([r.ids for r in sample]),
            ref_vals, ref_ids, at_served, norms))
        R = ref.projection(s["index"], system.dim, sk["k"], sk["block_d"])
        X = ref.rows_at(gen, system.data_key, sources, n=system.batch_rows,
                        d=system.dim, gen_params=system.gen_params)
        ref_U, ref_M = ref.sketch(X, R, p=sk["p"], precision="highest")
        numbers.update(check.sketch_gap(stored[0], ref_U, ref_M))
    log(f"reference compared {len(sample)} answers")
    verdict = check.judge(numbers, cell.limits["limits"])
    correct = all(v["ok"] for v in verdict)

    # ------------------------------------------------------ the line
    dev = devices[0]
    result = {
        "correct": correct,
        "attempted": len(requests),
        "failed": failed,
        "metrics": {},
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": len(devices), "memory_peak_bytes": max(mem)},
    }
    if trace:
        summary = tracing.summarize(
            tracing.read_xplane(tracing.find_xplane(trace_dir)),
            [d.id for d in devices])
        shutil.rmtree(trace_dir, ignore_errors=True)
        lo, hi = marker.bounds
        win = Window(
            cell=cell, config=cfg, traffic=traffic, requests=requests,
            rows=rows_d, batches=batches_d, spans=spans,
            traced_batches=_batch_weights(spans, lo, hi),
            trace=summary, compiles=window_compiles, live_rows=live_rows,
            counters=counters,
            chips=len(devices),
            peak=roofline.peaks(dev.device_kind) if require_chip else None,
            packed_width=(sk["p"] - 1) * sk["k"])
        for m in cell.per_layer:
            v = bench.metric_reader(m.name)(win)
            if v is not None:
                result["metrics"][m.name] = {"value": float(v),
                                             "unit": m.unit}
        result["device"]["busy_s"] = summary.mean_busy_s
        result["device"]["window_s"] = summary.window_s
        result["device"]["trace_events"] = summary.events
        result["breakdown"] = {"device_ops": summary.device_ops,
                               "idle_gaps": summary.idle_gaps}
    else:
        for m in cell.end_to_end:
            v = e2e[m.name]
            result["metrics"][m.name] = {
                "value": v if math.isfinite(v) else None, "unit": m.unit}
    result["checks"] = {v["name"]: {"value": _finite(v["value"]),
                                    "limit": v["limit"]} for v in verdict}
    for v in verdict:
        log(f"check {v['name']}: {v['value']!r} <= {v['limit']!r} "
            f"{'ok' if v['ok'] else 'FAILED'}")
    return result
