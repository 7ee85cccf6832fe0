"""Rows per flushed batch over the window: the deltas of the batcher's
always-on ``batcher.rows`` / ``batcher.batches`` counters."""


def read(w):
    return w.rows / w.batches if w.batches else None
