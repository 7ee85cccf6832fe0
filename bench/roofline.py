"""The yardstick for a kernel's share of its roofline.

The least time a chip could spend on some work is the larger of its
operations over the peak FLOP/s and its bytes over the peak bytes/s.  A
share of the roofline is that least time over the time the device spent.
Peaks are kept in ``peaks.json``, keyed by the ``device_kind`` JAX reports;
a device that is not in the table is an error, never a default.
"""

from __future__ import annotations

import json
from pathlib import Path

PEAKS_FILE = Path(__file__).resolve().parent / "peaks.json"


def peaks(device_kind: str, path: Path = PEAKS_FILE) -> dict:
    """{"flops_per_s", "bytes_per_s", "hbm_bytes", "source"} of one chip."""
    with open(path) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in {path} "
                       f"(known: {sorted(table)})")
    return table[device_kind]


def pairwise_lp_work(q: int, n: int, w: int) -> tuple:
    """(FLOPs, bytes) the packed l_p strip needs for q query rows against n
    corpus rows of packed width w, unpadded: one multiply-add per term, and
    reading A (q, w), B (n, w), both norm vectors and writing D (q, n), all
    float32."""
    flops = 2.0 * q * n * w
    nbytes = 4.0 * (n * w + q * w + q * n + n + q)
    return flops, nbytes


def least_seconds(flops: float, nbytes: float, peak: dict) -> tuple:
    """(seconds, "compute" | "memory"): the roofline's time and its bound."""
    t_f = flops / peak["flops_per_s"]
    t_b = nbytes / peak["bytes_per_s"]
    return (t_f, "compute") if t_f >= t_b else (t_b, "memory")
