"""The ``pairwise_lp`` Pallas kernel's share of its roofline: the least
time its unpadded work needs on these chips over the device time of its
events (op names containing ``pairwise_lp``) in the traced window.

Work per batch of q rows against the n live rows, packed width W: 2qnW
FLOPs and 4(nW + qW + qn + n + q) bytes (``bench/roofline.py``); peaks
from ``bench/peaks.json`` by device kind.  Nothing to read when no kernel
event ran.
"""

from bench.roofline import least_seconds, pairwise_lp_work


def read(w):
    kernel_s = w.trace.kernel_s.get("pairwise_lp", 0.0)
    if kernel_s <= 0 or not w.traced_batches or w.peak is None:
        return None
    flops = nbytes = 0.0
    for rows, share in w.traced_batches:
        f, b = pairwise_lp_work(rows, w.live_rows, w.packed_width)
        flops += share * f
        nbytes += share * b
    least, _ = least_seconds(flops, nbytes, w.peak)
    return 100.0 * least / kernel_s
