"""Mean time a request waited in the batcher before its batch's flush
started: each ``batcher.query`` span's ``queue_wait_ms`` (the mean over its
requests), weighted by its ``requests``, on the host clock.  Nothing to
read from a program whose spans carry no such attribute."""

from bench.program_spans import batches


def read(w):
    pairs = [(b.attrs["requests"], b.attrs["queue_wait_ms"])
             for b in batches() if "queue_wait_ms" in b.attrs]
    n = sum(k for k, _ in pairs)
    return sum(k * v for k, v in pairs) / n if n else None
