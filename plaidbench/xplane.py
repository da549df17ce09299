"""Reduce a profiler trace (``.xplane.pb``) to the benchmark's numbers.

Reads the file with ``jax.profiler.ProfileData`` alone.  Device planes are
``/device:TPU:<n>``; on each, the ``XLA Ops`` line holds one event per
operation run and the ``XLA Modules`` line one per program run.  The host
plane holds the benchmark's own ``jax.profiler.TraceAnnotation`` spans
(``plaidbench.*``), on the same clock.

* busy: the union of operation intervals on a chip, clipped to the window
  (the ``plaidbench.window`` span), averaged over the chips that ran any;
* idle gaps: the holes in that union on the first chip, each named by the
  innermost ``plaidbench.*`` span the host was in at the gap's middle
  (``host_between_calls`` where it was in none);
* device ops: summed durations per operation on the first chip, each
  labelled by its HLO instruction without layouts (``label``).

On the TPU an operation's event is named by its whole HLO instruction
(``%fusion.18 = f32[..] fusion(..), kind=.., calls=..``); a Pallas kernel's
is a ``tpu_custom_call`` whose instruction is named after the kernel's
Python function (``%centroid_interaction_batched.2 = ..``).
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re

import numpy as np

_DEVICE = re.compile(r"^/device:TPU:(\d+)$")
_LAYOUT = re.compile(r"\{[^{}]*\}")
WINDOW = "plaidbench.window"


@dataclasses.dataclass
class Event:
    name: str
    start: float  # ns
    end: float  # ns
    stats: dict


@dataclasses.dataclass
class TraceSummary:
    window: tuple[float, float]  # ns
    ops: dict  # chip -> [Event] (XLA Ops, clipped to the window)
    modules: dict  # chip -> [Event] (XLA Modules, in the window)
    spans: list  # host plaidbench.* spans (Event)

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) / 1e9

    @property
    def chips(self) -> list[int]:
        return sorted(c for c, ev in self.ops.items() if ev)

    def busy_intervals(self, chip: int) -> np.ndarray:
        return _union([(e.start, e.end) for e in self.ops.get(chip, [])])

    @property
    def busy_s(self) -> float:
        chips = self.chips
        if not chips:
            return 0.0
        return float(np.mean([
            (iv[:, 1] - iv[:, 0]).sum() / 1e9 if len(iv) else 0.0
            for iv in (self.busy_intervals(c) for c in chips)
        ]))

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def first_chip(self) -> int:
        return self.chips[0] if self.chips else 0

    def op_seconds(self, match) -> float:
        """Summed device seconds on the first chip of ops whose name
        satisfies ``match``."""
        return sum(
            (e.end - e.start) for e in self.ops.get(self.first_chip(), [])
            if match(e.name)
        ) / 1e9

    def op_count(self, match) -> int:
        return sum(1 for e in self.ops.get(self.first_chip(), []) if match(e.name))

    def module_events(self, match) -> list[Event]:
        return [e for e in self.modules.get(self.first_chip(), []) if match(e.name)]

    def top_ops(self, n: int = 10) -> list:
        agg: dict[str, float] = {}
        for e in self.ops.get(self.first_chip(), []):
            key = label(e.name)
            agg[key] = agg.get(key, 0.0) + (e.end - e.start) / 1e9
        return [[k, v] for k, v in sorted(agg.items(), key=lambda kv: -kv[1])[:n]]

    def idle_gaps(self, n: int = 10) -> list:
        """The ``n`` longest gaps in the first chip's busy union inside the
        window, each named by what the host was doing."""
        iv = self.busy_intervals(self.first_chip())
        lo, hi = self.window
        edges = [lo] + [x for pair in iv for x in pair] + [hi]
        gaps = [
            (edges[i], edges[i + 1]) for i in range(0, len(edges) - 1, 2)
            if edges[i + 1] > edges[i]
        ]
        gaps.sort(key=lambda g: g[0] - g[1])
        out = []
        for a, b in gaps[:n]:
            mid = (a + b) / 2
            inner = [
                s for s in self.spans
                if s.name != WINDOW and s.start <= mid <= s.end
            ]
            name = min(inner, key=lambda s: s.end - s.start).name if inner else "host_between_calls"
            out.append([name, (b - a) / 1e9])
        return out


def label(name: str, width: int = 160) -> str:
    """An operation's HLO instruction without layouts and attributes:
    ``%fusion.18 = f32[16777216,32] fusion(f32[16,262144,32] %bitcast.20, ..``."""
    text = _LAYOUT.sub("", _LAYOUT.sub("", name))
    for cut in (", kind=", ", custom_call_target=", ", calls=", ", to_apply=",
                ", condition=", ", dimensions="):
        text = text.split(cut)[0]
    return text[:width]


def kernel(prefix: str):
    """Matches the events of the Pallas kernel whose function is ``prefix``."""
    return lambda name: name.startswith(f"%{prefix}") and "tpu_custom_call" in name


def _union(intervals) -> np.ndarray:
    if not intervals:
        return np.zeros((0, 2))
    iv = np.array(sorted(intervals))
    merged = [list(iv[0])]
    for a, b in iv[1:]:
        if a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return np.array(merged)


def _events(line) -> list[Event]:
    out = []
    for e in line.events:
        try:
            stats = {k: v for k, v in e.stats}
        except Exception:  # noqa: BLE001 - stats are optional
            stats = {}
        out.append(Event(e.name, float(e.start_ns), float(e.start_ns + e.duration_ns), stats))
    return out


def find_xplane(logdir: str) -> str:
    files = sorted(glob.glob(os.path.join(logdir, "**", "*.xplane.pb"), recursive=True))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    return files[-1]


def reduce(path: str) -> TraceSummary:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    ops, modules, spans = {}, {}, []
    for plane in data.planes:
        m = _DEVICE.match(plane.name)
        if m:
            chip = int(m.group(1))
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops[chip] = _events(line)
                elif line.name == "XLA Modules":
                    modules[chip] = _events(line)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans += [e for e in _events(line) if e.name.startswith("plaidbench.")]
    windows = [s for s in spans if s.name == WINDOW]
    if windows:
        lo, hi = windows[0].start, windows[0].end
    else:
        every = [e for evs in ops.values() for e in evs]
        lo = min((e.start for e in every), default=0.0)
        hi = max((e.end for e in every), default=0.0)
    clip = {
        c: [Event(e.name, max(e.start, lo), min(e.end, hi), e.stats)
            for e in evs if e.end > lo and e.start < hi]
        for c, evs in ops.items()
    }
    mods = {
        c: [e for e in evs if e.end > lo and e.start < hi]
        for c, evs in modules.items()
    }
    return TraceSummary((lo, hi), clip, mods, spans)
