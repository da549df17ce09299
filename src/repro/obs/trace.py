"""Span tracing: thread-safe ring buffer -> Chrome trace-event JSON.

Every latency narrative in this repo used to be a hand-rolled
``time.perf_counter`` pair; this module makes spans first-class:

    tracer = obs.get_tracer()
    with tracer.span("dispatch", bucket=8):
        ...

Spans record onto a bounded ring (a deque with ``maxlen`` — a long-running
server keeps the most recent ``capacity`` spans at constant memory) under
one lock, and export as Chrome trace-event JSON — load the file in
Perfetto (https://ui.perfetto.dev) or ``chrome://tracing`` to see the
serving tier's queue-wait/pad/dispatch timeline exactly as the paper's
Fig. 2 shows the pipeline's stage timeline.

Each span gets a process-unique ``sid`` and the ``parent`` sid of the
innermost span open on its thread when it began, whichever ``Tracer``
holds that span: the server's spans and the facade's, recorded into two
tracers, still nest.  A live span (``span``) also enters
``jax.profiler.TraceAnnotation`` under its name and attributes, so any
profiler capture running at the time holds it on the device trace's
clock.  A retroactive span (``record``) has no such twin: it keeps the
tracer's clock, and a reader places it on the trace's.

Determinism hooks for tests: the wall clock is injectable (``clock=``
takes any ``() -> float`` seconds callable), so a test can drive spans
with a fake clock and assert exact ``ts``/``dur`` values.  The real
default is ``time.perf_counter`` (monotonic — spans never go backwards
under NTP slews).

``device_trace`` wraps ``jax.profiler.trace`` for sampled device-side
captures; the live spans of every tracer land in the capture beside the
device's operations.
"""
from __future__ import annotations

import contextlib
import itertools
import json
import os
import threading
import time
from collections import deque

from jax import profiler as _profiler

#: Chrome trace-event "complete event" phase — one event carries ts + dur.
_PH_COMPLETE = "X"
#: Instant-event phase (scope "t": thread-scoped tick mark).
_PH_INSTANT = "i"

#: Span ids, unique in the process (``next`` on a count is atomic under
#: the GIL).
_IDS = itertools.count(1)
#: Per thread, the sids of the live spans open on it, innermost last.
_OPEN = threading.local()


def _open_spans() -> list:
    stack = getattr(_OPEN, "stack", None)
    if stack is None:
        stack = _OPEN.stack = []
    return stack


class Span:
    """One recorded span: name, start (s), duration (s), thread, attrs, its
    id and its parent's (None at the top of its thread)."""

    __slots__ = ("name", "ts", "dur", "tid", "attrs", "sid", "parent")

    def __init__(self, name, ts, dur, tid, attrs, sid=None, parent=None):
        self.name = name
        self.ts = ts
        self.dur = dur
        self.tid = tid
        self.attrs = attrs
        self.sid = sid
        self.parent = parent

    def __repr__(self):  # pragma: no cover - debugging aid
        return f"Span({self.name!r}, ts={self.ts:.6f}, dur={self.dur:.6f})"


class Tracer:
    """Bounded, thread-safe span recorder with Chrome-trace export."""

    def __init__(self, capacity: int = 8192, clock=None):
        if capacity < 1:
            raise ValueError(f"capacity must be >= 1, got {capacity}")
        self.capacity = capacity
        self._clock = clock if clock is not None else time.perf_counter
        self._lock = threading.Lock()
        self._buf: deque[Span] = deque(maxlen=capacity)

    # ---- recording -------------------------------------------------------
    @contextlib.contextmanager
    def span(self, name: str, **attrs):
        """Context manager recording one complete span (exceptions still
        record — a failed dispatch is exactly the span you want to see),
        annotated on the profiler's clock while it is open."""
        stack = _open_spans()
        parent = stack[-1] if stack else None
        sid = next(_IDS)
        stack.append(sid)
        t0 = self._clock()
        try:
            with _profiler.TraceAnnotation(name, **attrs):
                yield self
        finally:
            t1 = self._clock()
            stack.pop()
            self._record(name, t0, t1 - t0, attrs, sid, parent)

    def instant(self, name: str, **attrs) -> None:
        """Zero-duration marker (generation bumps, shed events, ...)."""
        self._record(name, self._clock(), 0.0, attrs)

    def record(self, name: str, ts: float, dur: float, **attrs) -> None:
        """Record a span retroactively from explicit ``ts``/``dur`` seconds
        (same clock domain as ``clock``).  This is how queue-wait gets a
        span: the wait is only known at dispatch time, after it ended."""
        self._record(name, ts, max(dur, 0.0), attrs)

    def _record(self, name, ts, dur, attrs, sid=None, parent=None) -> None:
        if sid is None:
            stack = _open_spans()
            sid, parent = next(_IDS), (stack[-1] if stack else None)
        s = Span(
            name, ts, dur, threading.get_ident(), attrs or None, sid, parent
        )
        with self._lock:
            self._buf.append(s)

    # ---- reading ---------------------------------------------------------
    def spans(self, name: str | None = None) -> list[Span]:
        """Snapshot of recorded spans, oldest first (optionally by name)."""
        with self._lock:
            out = list(self._buf)
        if name is not None:
            out = [s for s in out if s.name == name]
        return out

    def __len__(self) -> int:
        with self._lock:
            return len(self._buf)

    def clear(self) -> None:
        with self._lock:
            self._buf.clear()

    def durations_ms(self, name: str) -> list[float]:
        """All recorded durations for ``name``, in milliseconds."""
        return [s.dur * 1e3 for s in self.spans(name)]

    def summary(self) -> dict:
        """Per-span-name {count, total_ms, mean_ms} rollup."""
        agg: dict[str, list] = {}
        for s in self.spans():
            agg.setdefault(s.name, []).append(s.dur)
        return {
            name: dict(
                count=len(durs),
                total_ms=sum(durs) * 1e3,
                mean_ms=sum(durs) / len(durs) * 1e3,
            )
            for name, durs in sorted(agg.items())
        }

    # ---- export ----------------------------------------------------------
    def to_chrome_trace(self) -> dict:
        """Chrome trace-event JSON object (Perfetto-loadable).

        ``ts``/``dur`` are microseconds per the trace-event spec; complete
        spans use ``ph: "X"``, instants ``ph: "i"``.  ``id`` is the span's
        sid and ``args.parent`` its parent's, where it has one.
        """
        pid = os.getpid()
        events = []
        for s in self.spans():
            ev = dict(
                name=s.name,
                ph=_PH_COMPLETE if s.dur > 0 else _PH_INSTANT,
                ts=s.ts * 1e6,
                pid=pid,
                tid=s.tid,
                id=s.sid,
            )
            if ev["ph"] == _PH_COMPLETE:
                ev["dur"] = s.dur * 1e6
            else:
                ev["s"] = "t"
                ev["dur"] = 0.0
            args = {k: _jsonable(v) for k, v in (s.attrs or {}).items()}
            if s.parent is not None:
                args["parent"] = s.parent
            if args:
                ev["args"] = args
            events.append(ev)
        return dict(traceEvents=events, displayTimeUnit="ms")

    def export(self, path: str) -> int:
        """Write the Chrome trace JSON; returns the number of events."""
        trace = self.to_chrome_trace()
        with open(path, "w") as f:
            json.dump(trace, f)
        return len(trace["traceEvents"])

    # ---- device capture --------------------------------------------------
    @contextlib.contextmanager
    def device_trace(self, logdir: str):
        """Sampled device capture via ``jax.profiler.trace`` alongside the
        host spans (one ``device_trace`` span brackets the capture).  A
        profiler that fails raises: a capture that silently recorded nothing
        would read as an idle device."""
        with self.span("device_trace", logdir=logdir):
            with _profiler.trace(logdir):
                yield


def _jsonable(v):
    if isinstance(v, (str, int, float, bool)) or v is None:
        return v
    try:
        import numpy as np

        if isinstance(v, np.generic):
            return v.item()
    except ImportError:  # pragma: no cover
        pass
    return str(v)


#: The zero-plumbing process-wide tracer.
_TRACER = Tracer()


def get_tracer() -> Tracer:
    return _TRACER
