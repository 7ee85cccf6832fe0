"""Mean time per flushed batch in the sharded index's stage 2 on the host
(``index.fan.stage2`` spans under each ``batcher.query`` span): the
concatenation of the shards' candidate lists, ``_finite_k``, the
(value, position) lexsort re-rank and the id mapping.  Nothing to read from
a program without that span."""

from bench.program_spans import per_batch_ms


def read(w):
    return per_batch_ms("index.fan.stage2")
