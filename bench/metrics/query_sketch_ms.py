"""Mean time per flushed batch spent sketching the query rows: the
``index.sketch`` spans under each ``batcher.query`` span, on the host
clock.  Nothing to read from a program without that span."""

from bench.program_spans import per_batch_ms


def read(w):
    return per_batch_ms("index.sketch")
