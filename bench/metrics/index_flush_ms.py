"""Mean duration of a batch's flush through the index (planner, stage 1,
stage 2): the ``batcher.query`` spans of the window, on the host clock."""


def read(w):
    if not w.spans:
        return None
    return 1e3 * sum(t1 - t0 for t0, t1, _ in w.spans) / len(w.spans)
