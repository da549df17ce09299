"""The one load generator, driven by a traffic file's parameters.

``open_loop``: single-query requests arrive on a schedule fixed before the
window, whatever the system does, through the program's ``BatchingServer``.
Each request is timed from when it was due to its result in the client's
hand, so a stall also delays every later request; how late the generator
itself ran is recorded beside it.

``closed_loop``: one caller sends fixed-size batches to the retriever's
``search_batch`` back to back; the rate is queries completed over the
window's whole time.
"""
from __future__ import annotations

import dataclasses
import queue
import threading
import time

import numpy as np


def poisson_schedule(rate: float, seconds: float, seed: int) -> np.ndarray:
    """Due times (s from the window's start) of ``round(rate * seconds)``
    Poisson arrivals.  The gaps are the exponential distribution's
    quantiles, shuffled by ``seed``: every seed offers the same gaps, and so
    the same load, in another order."""
    n = max(1, int(round(rate * seconds)))
    gaps = -np.log1p(-(np.arange(n) + 0.5) / n) / rate
    np.random.default_rng((int(seed), 2)).shuffle(gaps)
    return np.cumsum(gaps)


@dataclasses.dataclass
class Window:
    """What the measured window did, per request (NaN where none came)."""

    n: int
    t0: float  # window start (clock seconds)
    t_end: float  # last completion or the window's close
    scores: list  # per request: (k,) scores or None
    pids: list  # per request: (k,) pids or None
    latency_s: np.ndarray | None = None  # open loop: done - due
    late_s: np.ndarray | None = None  # open loop: sent - due
    errors: list = dataclasses.field(default_factory=list)

    @property
    def answered(self) -> np.ndarray:
        return np.array([p is not None for p in self.pids])

    @property
    def seconds(self) -> float:
        return self.t_end - self.t0


def open_loop(submit, qs, due, *, clock=time.perf_counter, sleep=time.sleep,
              lead=0.05, grace=60.0) -> Window:
    """Submit ``qs[i]`` at ``t0 + due[i]`` through ``submit`` (returns a
    future with ``get(timeout)``) and collect results in order."""
    n = len(due)
    win = Window(n=n, t0=clock() + lead, t_end=0.0, scores=[None] * n, pids=[None] * n)
    done = np.full(n, np.nan)
    sent = np.full(n, np.nan)
    handed: queue.Queue = queue.Queue()
    close = win.t0 + float(due[-1]) + grace

    def collect():
        for _ in range(n):
            i, fut = handed.get()
            if fut is None:
                continue
            try:
                res = fut.get(timeout=max(close - clock(), 0.0))
            except Exception as e:  # noqa: BLE001 - every failure is counted
                win.errors.append(repr(e))
                continue
            done[i] = clock()
            win.scores[i], win.pids[i] = np.asarray(res.scores), np.asarray(res.pids)

    collector = threading.Thread(target=collect, daemon=True)
    collector.start()
    for i in range(n):
        wait = win.t0 + due[i] - clock()
        if wait > 0:
            sleep(wait)
        sent[i] = clock()
        try:
            fut = submit(qs[i])
        except Exception as e:  # noqa: BLE001 - a refused request is a miss
            win.errors.append(repr(e))
            fut = None
        handed.put((i, fut))
    collector.join(timeout=max(close - clock(), 0.0) + 1.0)
    if collector.is_alive():
        win.errors.append("collector still waiting at the close")
    win.t_end = float(np.nanmax(done)) if np.isfinite(done).any() else clock()
    win.latency_s = done - (win.t0 + due)
    win.late_s = sent - (win.t0 + due)
    return win


def closed_loop(search, qs, batch: int, seconds: float, *, clock=time.perf_counter,
                annotate=None) -> Window:
    """Batches of ``batch`` consecutive queries (wrapping round the pool) to
    ``search`` back to back until ``seconds`` have passed; the last batch
    ends the window."""
    n_pool = len(qs) // batch * batch
    scores, pids = [], []
    t0 = clock()
    i = 0
    while clock() - t0 < seconds:
        j = (i * batch) % n_pool
        if annotate is None:
            s, p = search(qs[j : j + batch])
        else:
            with annotate():
                s, p = search(qs[j : j + batch])
        scores.extend(np.asarray(s))
        pids.extend(np.asarray(p))
        i += 1
    return Window(n=len(pids), t0=t0, t_end=clock(), scores=scores, pids=pids)

