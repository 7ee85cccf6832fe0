"""Mean time per flushed batch in the sharded index's stage 1: the
``index.fan.stage1`` spans that carry ``shards`` (the sharded fans' own;
the single-host fan's carry none) under each ``batcher.query`` span, on the
host clock.  On the stacked route it covers the query pack, the one
``shard_map`` dispatch and the blocking copy of the (shards, q, k)
candidate lists.  Nothing to read where no batch went through a sharded
fan."""

from bench.program_spans import batches


def read(w):
    flushed = batches()
    per = [sum(s.duration_s for s in b.find("index.fan.stage1")
               if "shards" in s.attrs) for b in flushed]
    if not any(per):
        return None
    return 1e3 * sum(per) / len(flushed)
