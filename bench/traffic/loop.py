"""The one load generator: drives ``ServingFrontend.submit`` and ``pump``
from a single thread, in a closed or an open loop.

- ``"mode": "closed"``: ``clients`` callers, each with one request in
  flight; a caller sends its next query as soon as its answer is back.
- ``"mode": "open"``: ``round(rate * seconds)`` requests at Poisson
  arrival times, scaled to fill the window exactly, so every seed offers
  the same number of requests. Each request is timed from its scheduled
  arrival (``submit(t_submit=...)``), so a wait that a slow dispatch
  forces on later arrivals is counted (no coordinated omission), and how
  late each ``submit`` ran behind its schedule is recorded.

The loop follows ``repro.retrieval.frontend.replay_open_loop``, copied so
that the yardstick cannot change with the program, with that lag added.
Every call into the frontend runs inside a host span
(``bench.submit``/``bench.pump``/``bench.collect``/``bench.sleep``), so
the trace can say what the host was doing in each device gap.
"""
from __future__ import annotations

import time

import numpy as np
from jax.profiler import TraceAnnotation

SLEEP_MAX_S = 0.005


class Request:
    """One request: which pool query, when it was due and sent, and the
    frontend's handle (``t_done``, answer or error)."""
    __slots__ = ("query", "t_sched", "t_sent", "handle")

    def __init__(self, query: int, t_sched: float, t_sent: float, handle):
        self.query, self.t_sched, self.t_sent = query, t_sched, t_sent
        self.handle = handle


def _submit(fe, queries, lens, i: int, t_sched: float):
    n = lens[i]
    with TraceAnnotation("bench.submit"):
        return fe.submit(queries[i, :n], t_submit=t_sched)


def closed(fe, queries, lens, mix: dict, seconds: float):
    """``clients`` callers for ``seconds``, taking the pool's queries in
    turn; returns (requests, t0, t1)."""
    clock = fe.clock
    n_pool = len(queries)
    nxt = 0
    t0 = clock()
    t_end = t0 + seconds
    live, out = [], []
    for _ in range(mix["clients"]):
        i = nxt % n_pool
        nxt += 1
        r = Request(i, t0, t0, _submit(fe, queries, lens, i, t0))
        live.append(r)
        out.append(r)
    while True:
        with TraceAnnotation("bench.pump"):
            fe.pump()
        now = clock()
        if now >= t_end:
            break
        with TraceAnnotation("bench.collect"):
            for c, r in enumerate(live):
                if r.handle.done():
                    i = nxt % n_pool
                    nxt += 1
                    t = clock()
                    live[c] = Request(i, t, t, _submit(fe, queries, lens, i,
                                                       t))
                    out.append(live[c])
    return out, t0, t_end


def arrivals(rate: float, seconds: float, seed: int) -> np.ndarray:
    """``round(rate * seconds)`` arrival offsets in [0, seconds): Poisson
    gaps, scaled so that the count is the same for every seed."""
    n = int(round(rate * seconds))
    gaps = np.random.default_rng([int(seed), 3]).exponential(size=n + 1)
    return seconds * np.cumsum(gaps)[:n] / gaps.sum()


def open_(fe, queries, lens, mix: dict, seconds: float, seed: int):
    """Poisson arrivals at ``rate`` req/s for ``seconds``, the pool's
    queries in turn, then the queue drained; returns (requests, t0, t1)."""
    clock = fe.clock
    due = arrivals(mix["rate"], seconds, seed)
    n_pool = len(queries)
    out = []
    i, n = 0, len(due)
    t0 = clock()
    while i < n or fe.pending:
        now = clock()
        while i < n and t0 + due[i] <= now:
            q = i % n_pool
            t_sched = t0 + due[i]
            t_sent = clock()
            out.append(Request(q, t_sched, t_sent,
                               _submit(fe, queries, lens, q, t_sched)))
            i += 1
        with TraceAnnotation("bench.pump"):
            served = fe.pump()
        if served:
            continue
        waits = []
        if i < n:
            waits.append(t0 + due[i] - clock())
        deadline = fe.next_deadline()
        if deadline is not None:
            waits.append(deadline - clock())
        wait = min(waits) if waits else 0.0
        if wait > 0:
            with TraceAnnotation("bench.sleep"):
                time.sleep(min(wait, SLEEP_MAX_S))
    return out, t0, t0 + seconds


def drive(fe, queries, lens, mix: dict, seconds: float, seed: int):
    """Run the mix for ``seconds``. Returns (requests, t0, t1): every
    request sent, and the window on the frontend's clock."""
    if mix["mode"] == "closed":
        return closed(fe, queries, lens, mix, seconds)
    if mix["mode"] == "open":
        return open_(fe, queries, lens, mix, seconds, seed)
    raise ValueError(f"unknown traffic mode {mix['mode']!r}")
