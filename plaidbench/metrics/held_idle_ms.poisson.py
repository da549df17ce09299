"""Serving: the chip's idle time while a request was in the server, per
answered request, in ms.

1. The program's spans are placed on the trace's clock: each benchmark
   ``plaidbench.dispatch`` annotation (trace clock) holds exactly one facade
   ``retrieval.search_batch`` span (the program's clock); paired in order,
   the offset is the median of their start differences.  Where the counts
   differ, or the differences spread more than 1 ms, there is no reading:
   a wrong join must not give a number.
2. A request is in the server from the start of its ``serve.queue_wait``
   to the end of its batch's ``serve.dispatch`` (joined by ``batch``).
3. The first chip's idle time inside the union of those intervals, over
   the requests answered.
"""
import sys

import numpy as np

from plaidbench import stages, xplane

SPREAD_S = 1e-3


def _log(msg):
    print(f"[held_idle] {msg}", file=sys.stderr, flush=True)


def _offset(ctx):
    """Trace-clock ns minus program-clock s * 1e9, or None."""
    ann = sorted(
        (s for s in ctx["trace"].spans if s.name == "plaidbench.dispatch"),
        key=lambda s: s.start,
    )
    fac = sorted(stages.facade_spans(ctx, "retrieval.search_batch"), key=lambda s: s.ts)
    if not ann or len(ann) != len(fac):
        _log(f"no reading: {len(ann)} plaidbench.dispatch, {len(fac)} retrieval.search_batch")
        return None
    diff = np.array([a.start - f.ts * 1e9 for a, f in zip(ann, fac)])
    spread = (diff.max() - diff.min()) / 1e9
    _log(f"pairs={len(ann)} spread_ms={spread * 1e3:.6f}")
    if spread > SPREAD_S:
        return None
    return float(np.median(diff))


def _length(iv) -> float:
    return float((iv[:, 1] - iv[:, 0]).sum()) if len(iv) else 0.0


def _overlap(a, b) -> float:
    """Length of the intersection of two sorted disjoint interval arrays."""
    i = j = 0
    total = 0.0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i, 0], b[j, 0]), min(a[i, 1], b[j, 1])
        total += max(hi - lo, 0.0)
        if a[i, 1] < b[j, 1]:
            i += 1
        else:
            j += 1
    return total


def read(ctx):
    tr, win = ctx["trace"], ctx["window"]
    answered = int(np.sum(win.answered))
    if not answered:
        return None
    offset = _offset(ctx)
    if offset is None:
        return None
    inside = [s for s in ctx["spans"] if win.t0 <= s.ts <= win.t_end]
    dispatch_end = {
        s.attrs["batch"]: s.ts + s.dur
        for s in inside if s.name == "serve.dispatch" and s.attrs and "batch" in s.attrs
    }
    held = []
    for s in inside:
        if s.name != "serve.queue_wait":
            continue
        end = dispatch_end.get((s.attrs or {}).get("batch"))
        if end is None:
            _log("no reading: a serve.queue_wait names no dispatched batch")
            return None
        held.append((s.ts * 1e9 + offset, end * 1e9 + offset))
    lo, hi = tr.window
    held = xplane._union([(max(a, lo), min(b, hi)) for a, b in held if b > lo and a < hi])
    busy = tr.busy_intervals(tr.first_chip())
    idle_ns = _length(held) - _overlap(held, busy)
    _log(f"requests={answered} held_s={_length(held) / 1e9:.6f} idle_held_s={idle_ns / 1e9:.6f}")
    return idle_ns / 1e6 / answered
