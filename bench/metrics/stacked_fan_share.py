"""Share of the window's sharded stage-1 fans that the stacked
``shard_map`` route served: the window's deltas of the program's counters
``index.stage1_parallel`` and ``index.stage1_dispatch``, as
100 * parallel / (parallel + dispatch).  Nothing to read where neither
counted (no sharded fan ran, or the program keeps no such counters)."""


def read(w):
    counters = getattr(w, "counters", None) or {}
    parallel = counters.get("index.stage1_parallel", 0)
    dispatch = counters.get("index.stage1_dispatch", 0)
    if parallel + dispatch <= 0:
        return None
    return 100.0 * parallel / (parallel + dispatch)
