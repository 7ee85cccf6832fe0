"""Device operations run per flushed batch: ``XLA Ops`` events in the
traced window, per chip, over the batches flushed in it (a batch that
straddles an end of the window counts by its share inside)."""


def read(w):
    batches = sum(share for _, share in w.traced_batches)
    if not batches:
        return None
    ops = sum(w.trace.ops[c] for c in w.trace.chips) / len(w.trace.chips)
    return ops / batches if ops else None
