"""The comparison that decides ``correct``.

Every number is compared with a limit of its own from
``bench/limits/<workload>.json``; the run is correct when each number is at
or under its limit.  The numbers:

- ``failed``: requests of the window that raised or never answered (0).
- ``sketch_gap``: stored sketch rows U of the compared queries' source
  rows against a fresh reference sketch, worst |dU| / ||x^j||_2.  (The
  moments are sums with no matmul; an error in them moves the values.)
- ``answer_gap``: each request's top-k against the reference's, rank by
  rank, worst |d| / ||q||_p^p over two gaps: the served value against the
  reference's value at that rank, and, where the served id differs, the
  reference's own value for the served id against it.  Both lists are
  sorted, so a swap of two tied ids moves nothing; an id that is no corpus
  row, or repeats in a row, reads infinity.  A request answered with
  another request's rows fails it.
"""

from __future__ import annotations

import math
from typing import Dict, List

import numpy as np

TINY = float(np.finfo(np.float32).tiny)


def sketch_gap(U, ref_U, ref_M) -> Dict[str, float]:
    U, ref_U = np.asarray(U, np.float64), np.asarray(ref_U, np.float64)
    if U.shape != ref_U.shape:
        return {"sketch_gap": math.inf}
    scale = np.sqrt(np.asarray(ref_M, np.float64))[:, :, None] + TINY
    return {"sketch_gap": float((np.abs(U - ref_U) / scale).max())}


def answer_gap(values, ids, ref_values, ref_ids, ref_at_served,
                norms) -> Dict[str, float]:
    """(q, k) served values/ids against the reference's top-k, its value at
    each served id and each query's ||q||_p^p."""
    values = np.asarray(values, np.float64)
    ids = np.asarray(ids)
    ref_values = np.asarray(ref_values, np.float64)
    at = np.asarray(ref_at_served, np.float64)
    scale = np.asarray(norms, np.float64)[:, None] + TINY
    if values.shape != ref_values.shape or ids.shape != ref_values.shape:
        return {"answer_gap": math.inf}
    if not np.isfinite(values).all():
        value_gap = math.inf
    else:
        value_gap = float((np.abs(values - ref_values) / scale).max())
    differ = ids != np.asarray(ref_ids)
    id_gap = 0.0
    if differ.any():
        gap = np.abs(at - ref_values) / scale
        id_gap = float(np.where(differ, np.nan_to_num(gap, nan=math.inf),
                                0.0).max())
    if any(len(set(r)) != len(r) for r in ids.tolist()):
        id_gap = math.inf
    return {"answer_gap": max(value_gap, id_gap)}


def judge(numbers: Dict[str, float], limits: Dict[str, float]) -> List[dict]:
    """[{"name", "value", "limit", "ok"}, ...] for every limit; a number
    that was not read fails."""
    out = []
    for name, limit in limits.items():
        v = numbers.get(name, math.inf)
        ok = bool(v is not None and not math.isnan(v) and v <= limit)
        out.append({"name": name, "value": v, "limit": limit, "ok": ok})
    return out
