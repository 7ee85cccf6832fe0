"""The whole query path's share of the chips' roofline: the least time the
batches flushed in the traced window need for their distance work (the
same work as ``pairwise_lp_roofline``) over the traced window times the
chips.  It bounds every kernel's share from below, also when a kernel
leaves the path."""

from bench.roofline import least_seconds, pairwise_lp_work


def read(w):
    if not w.traced_batches or w.trace.window_s <= 0 or w.peak is None:
        return None
    flops = nbytes = 0.0
    for rows, share in w.traced_batches:
        f, b = pairwise_lp_work(rows, w.live_rows, w.packed_width)
        flops += share * f
        nbytes += share * b
    least, _ = least_seconds(flops, nbytes, w.peak)
    return 100.0 * least / (w.trace.window_s * w.chips)
