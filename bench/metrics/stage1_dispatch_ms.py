"""Mean time per flushed batch in which the host issues the stage-1 fan's
strips (``index.fan.dispatch`` spans under each ``batcher.query`` span, on
the host clock).  Dispatch is asynchronous: the device work it queues is
waited for under ``stage1_collect_ms``.  Nothing to read from a program
without that span."""

from bench.program_spans import per_batch_ms


def read(w):
    return per_batch_ms("index.fan.dispatch")
