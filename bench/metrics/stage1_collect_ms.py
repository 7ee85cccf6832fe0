"""Mean time per flushed batch in which the host waits for the stage-1
candidate lists and brings them over (``index.fan.collect`` spans under
each ``batcher.query`` span, on the host clock): the device work still
outstanding when dispatch ended.  Nothing to read from a program without
that span."""

from bench.program_spans import per_batch_ms


def read(w):
    return per_batch_ms("index.fan.collect")
