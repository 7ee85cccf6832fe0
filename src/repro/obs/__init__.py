"""``repro.obs`` — low-overhead observability for the serving stack.

Three pieces, wired together:

  * :mod:`repro.obs.trace` — a monotonic-clock span tracer.  Stages wrap in
    ``with obs.span("index.fan.stage1", shards=4): ...``; spans nest into a
    per-thread tree, the root mints a process-unique trace id, and finished
    roots flow to registered sinks.  Each real span is also a profiler
    annotation, so a ``jax.profiler`` capture shows it on the device
    trace's clock.  **Disabled by default**: ``span()`` then returns
    one shared no-op object — the hot path pays a global load and a branch,
    nothing else.
  * :mod:`repro.obs.metrics` — a process-global registry of counters,
    gauges, and fixed-bucket latency histograms (p50/p95/p99 summaries,
    ``snapshot()`` dict, Prometheus text exposition, optional stdlib HTTP
    scrape endpoint).  Counters are always live (they are the serving
    stats), histograms fill from spans only while tracing is enabled.
    The scheduler (``repro.serve``) reports here too: ``scheduler.*``
    admission/shed/deadline counters, the ``scheduler.queue_depth`` gauge,
    and the ``scheduler.deadline_slack_ms`` / ``scheduler.shed_rows``
    histograms all land in the same registry the operator scrapes.
  * :mod:`repro.obs.slowlog` — a bounded worst-N log of query traces,
    attached as a tracer sink and surfaced via ``SketchIndex.stats()``.

``obs.enable()`` / ``obs.disable()`` flip the whole layer; the benchmark
suite's ``obs_overhead`` row pins the enabled-vs-disabled query latency
ratio, and the disabled path is covered by an allocation test.
"""

from __future__ import annotations

from . import metrics, slowlog, trace
from .metrics import REGISTRY, Counter, Gauge, Histogram, MetricsRegistry
from .slowlog import GLOBAL_SLOW_LOG, SlowQueryLog
from .trace import NULL_SPAN, Span, current_trace_id, span

__all__ = [
    "trace", "metrics", "slowlog",
    "span", "Span", "NULL_SPAN", "current_trace_id",
    "REGISTRY", "Counter", "Gauge", "Histogram", "MetricsRegistry",
    "SlowQueryLog", "GLOBAL_SLOW_LOG",
    "enable", "disable", "enabled",
]

# the global slow log sees every finished root span (it filters for queries)
trace.add_sink(GLOBAL_SLOW_LOG.offer)


def enable(jax_scope: bool = False) -> None:
    """Turn tracing (and with it span-fed histograms + the slow-query log)
    on.  Spans always reach a running ``jax.profiler`` capture as
    annotations; ``jax_scope`` is accepted and ignored (the benchmark
    harness still passes it)."""
    trace.enable()


def disable() -> None:
    trace.disable()


def enabled() -> bool:
    return trace.enabled()
