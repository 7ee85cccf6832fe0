"""The closed loop: each client sends one request, waits for its answer,
then sends the next, until the window closes and the round open at the
close is complete: a client that finds the window closed sends on while it
has sent fewer requests than the most any client had sent when the close
was first seen.  Clients that each wait for their answer move in rounds,
so a close that falls while some have resubmitted and others have not
would otherwise leave the last batch short of its round.

A request's latency runs from its send to its answer as a host numpy array.
Requests sent before the window closes are waited for after it; one that
fails, or never answers, counts as infinitely late.
"""

from __future__ import annotations

import dataclasses
import math
import threading
import time
from contextlib import nullcontext
from typing import Callable, List, Optional, Tuple

import numpy as np


@dataclasses.dataclass
class Request:
    client: int
    query: int                       # row of the query pool
    t_send: float
    t_done: float = math.nan
    values: Optional[np.ndarray] = None
    ids: Optional[np.ndarray] = None
    error: Optional[str] = None

    @property
    def ok(self) -> bool:
        return self.error is None and self.ids is not None

    @property
    def latency_s(self) -> float:
        return self.t_done - self.t_send if self.ok else math.inf


def client_orders(rng: np.random.Generator, pool: int, clients: int):
    """Client c's queries, in order: a seeded permutation of the pool dealt
    round-robin, so every seed sends the same set in another order."""
    perm = rng.permutation(pool)
    return [perm[c::clients] for c in range(clients)]


def run_closed_loop(send: Callable, pool: np.ndarray, orders, seconds: float,
                    *, drain_s: float = 60.0, annotate: bool = False,
                    at: Optional[Tuple[float, Callable[[], None]]] = None):
    """Drive ``send(row (1, d)) -> (values, ids)`` from ``len(orders)``
    client threads for ``seconds`` and the round open at the close;
    returns (requests, t_open, t_close, t_drained) on
    ``time.perf_counter``.

    ``at=(s, fn)`` calls ``fn()`` from the calling thread ``s`` seconds
    after the window opened, or when the clients are done if that is
    sooner, while the clients run on.  A client that has not answered
    ``drain_s`` after the window closed (or after ``fn`` returned, if
    later) is left behind: its request stays unanswered and counts as
    failed."""
    requests: List[Request] = []
    lock = threading.Lock()
    go = threading.Event()
    bounds = {}
    sent = [0] * len(orders)
    last_round = []

    def more(c: int) -> bool:
        with lock:
            if time.perf_counter() < bounds["close"]:
                return True
            if not last_round:
                last_round.append(max(sent))
            return sent[c] < last_round[0]

    def trace(name):
        if not annotate:
            return nullcontext()
        import jax

        return jax.profiler.TraceAnnotation(name)

    def client(c: int):
        order = orders[c]
        go.wait()
        i = 0
        while more(c):
            qi = int(order[i % len(order)])
            i += 1
            req = Request(client=c, query=qi, t_send=time.perf_counter())
            with lock:
                requests.append(req)
                sent[c] += 1
            try:
                with trace("bench.request"):
                    vals, ids = send(pool[qi:qi + 1])
                    vals, ids = np.asarray(vals), np.asarray(ids)
                req.values, req.ids = vals, ids
            except Exception as e:  # counted as failed, the loop goes on
                req.error = f"{type(e).__name__}: {e}"
            req.t_done = time.perf_counter()

    threads = [threading.Thread(target=client, args=(c,), daemon=True,
                                name=f"bench-client-{c}")
               for c in range(len(orders))]
    for t in threads:
        t.start()
    bounds["open"] = time.perf_counter()
    bounds["close"] = bounds["open"] + seconds
    go.set()
    deadline = bounds["close"] + drain_s
    if at is not None:
        t_at, fn = at
        for t in threads:
            t.join(max(0.0, bounds["open"] + t_at - time.perf_counter()))
        fn()
        deadline = max(deadline, time.perf_counter() + drain_s)
    for t in threads:
        t.join(max(0.0, deadline - time.perf_counter()))
    drained = time.perf_counter()
    with lock:
        out = list(requests)
    for r in out:
        if r.ids is None and r.error is None:
            r.error = "no answer within the drain"
    return out, bounds["open"], bounds["close"], drained


def percentile_ms(requests: List[Request], q: float) -> float:
    """Nearest-rank percentile of latency in ms, failures as +inf."""
    lat = sorted(r.latency_s for r in requests)
    if not lat:
        return math.nan
    rank = max(1, math.ceil(q / 100.0 * len(lat)))
    return lat[rank - 1] * 1e3


def rows_per_s(requests: List[Request]) -> float:
    """Rows answered over (last answer - first send), the whole window."""
    done = [r for r in requests if r.ok]
    if not done:
        return 0.0
    span = max(r.t_done for r in done) - min(r.t_send for r in requests)
    return sum(len(r.ids) for r in done) / span
