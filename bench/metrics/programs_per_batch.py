"""Device programs launched per flushed batch: ``XLA Modules`` events in
the traced window, per chip, over the batches flushed in it."""


def read(w):
    batches = sum(share for _, share in w.traced_batches)
    if not batches:
        return None
    progs = sum(w.trace.programs[c] for c in w.trace.chips) / len(w.trace.chips)
    return progs / batches if progs else None
