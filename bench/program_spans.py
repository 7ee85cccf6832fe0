"""The program's own spans of a traced window, for the span readers.

``obs.trace.recent_roots()`` holds the root spans that finished since the
harness enabled tracing for the window, warm-up excluded.  A program that
keeps no such record, or carries no span a reader asks for, gives nothing
to read: the readers then return None.
"""

from __future__ import annotations

from typing import List, Optional


def roots() -> list:
    """The finished root spans of the traced window ([] where the program
    keeps none)."""
    from repro.obs import trace

    recent = getattr(trace, "recent_roots", None)
    return recent() if recent is not None else []


def batches() -> list:
    """The window's ``batcher.query`` spans, one per flushed batch."""
    return [b for r in roots() for b in r.find("batcher.query")]


def per_batch_ms(name: str) -> Optional[float]:
    """Mean time per flushed batch inside spans named ``name`` under the
    batch's ``batcher.query`` span (None where no batch has one)."""
    flushed = batches()
    per: List[List[float]] = [[s.duration_s for s in b.find(name)]
                              for b in flushed]
    if not any(per):
        return None
    return 1e3 * sum(map(sum, per)) / len(flushed)
