"""Device time of the search program's stages, and the facade's spans.

The program runs each stage of a search under a ``jax.named_scope``
(``plaid.s1`` .. ``plaid.s4``), which its compiled instructions keep as
``op_name`` metadata.  A TPU trace keeps it too: in the ``.xplane.pb``,
the metadata of each ``XLA Ops`` event carries a ``tf_op`` stat, the
instruction's ``op_name`` (``jit(run_pipeline_impl)/plaid.s2.gather/gather:``).
``jax.profiler.ProfileData`` gives an event's own stats but not its
metadata's, so ``op_names`` reads them from the file's protobuf encoding.

Attribution, per run of the search program (an ``XLA Modules`` event of
``jit_run_pipeline_impl`` on the first chip):

* the run's events are the ``XLA Ops`` events inside it; only the
  outermost count, since an event inside another (a while loop's body in
  the loop) is time the outer one already holds;
* an event counts for the innermost ``plaid.*`` scope of its ``op_name``;
* an event with none (a while loop, a copy or a reduce-window the compiler
  wrote) counts for the first scope among the events inside it, else for
  the stage of the event before it in the run (the chip runs a program's
  operations in order), else for ``other``.

A trace whose operations name no ``plaid.*`` scope (a program without
stage scopes) reads None.
"""
from __future__ import annotations

import bisect
import pathlib
import sys

OTHER = "other"
#: the search program's ``XLA Modules`` events
MODULE = "run_pipeline"
#: where ``run.py`` records a ``--trace 1`` run's profile
TRACES = pathlib.Path(__file__).resolve().parent / ".traces"

# Field numbers of tsl/profiler/protobuf/xplane.proto.
_SPACE_PLANES = 1
_PLANE_NAME, _PLANE_EVENT_MD, _PLANE_STAT_MD = 2, 4, 5
_MAP_KEY, _MAP_VALUE = 1, 2
_MD_NAME, _EVENT_MD_STATS = 2, 5
_STAT_MD_ID, _STAT_STR, _STAT_REF = 1, 5, 7


def _varint(b, i):
    x = shift = 0
    while True:
        c = b[i]
        i += 1
        x |= (c & 0x7F) << shift
        shift += 7
        if c < 0x80:
            return x, i


def _fields(b):
    """``(field number, value)`` of a protobuf message: an int for a varint,
    a memoryview of the bytes otherwise."""
    i = 0
    while i < len(b):
        key, i = _varint(b, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(b, i)
        else:
            if wire == 2:
                n, i = _varint(b, i)
            else:
                n = {1: 8, 5: 4}[wire]
            v, i = b[i:i + n], i + n
        yield key >> 3, v


def _text(v) -> str:
    return bytes(v).decode("utf-8", "replace")


def op_names(path, plane: str) -> dict[str, str]:
    """Each event name of the trace's ``plane`` (``/device:TPU:0``) to its
    ``tf_op`` stat, ``""`` where it has none."""
    data = memoryview(pathlib.Path(path).read_bytes())
    for field, raw in _fields(data):
        if field != _SPACE_PLANES:
            continue
        fields = list(_fields(raw))
        name = next((_text(v) for f, v in fields if f == _PLANE_NAME), "")
        if name != plane:
            continue
        stat_names = {}
        for f, v in fields:
            if f == _PLANE_STAT_MD:
                entry = dict(_fields(v))
                md = dict(_fields(entry.get(_MAP_VALUE, b"")))
                stat_names[entry.get(_MAP_KEY, 0)] = _text(md.get(_MD_NAME, b""))
        tf_op = {k for k, v in stat_names.items() if v == "tf_op"}
        out = {}
        for f, v in fields:
            if f != _PLANE_EVENT_MD:
                continue
            md = list(_fields(dict(_fields(v)).get(_MAP_VALUE, b"")))
            op = ""
            for g, stat in md:
                if g != _EVENT_MD_STATS:
                    continue
                st = dict(_fields(stat))
                if st.get(_STAT_MD_ID) in tf_op:
                    op = (_text(st[_STAT_STR]) if _STAT_STR in st
                          else stat_names.get(st.get(_STAT_REF), ""))
            out[next((_text(x) for g, x in md if g == _MD_NAME), "")] = op
        return out
    return {}


def stage_of(op_name: str):
    """The innermost ``plaid.*`` scope of an ``op_name`` path, or None."""
    scopes = [
        part.split(":")[0] for part in op_name.split("/") if part.startswith("plaid.")
    ]
    return scopes[-1] if scopes else None


def trace_op_names(ctx):
    """``op_names`` of the run's trace, the newest under ``TRACES``, on its
    first chip (``ctx["op_names"]`` where a test gives them); None where
    there is none."""
    if "op_names" not in ctx:
        files = sorted(TRACES.glob("**/*.xplane.pb"), key=lambda p: p.stat().st_mtime)
        chip = ctx["trace"].first_chip()
        ctx["op_names"] = op_names(files[-1], f"/device:TPU:{chip}") if files else None
    return ctx["op_names"]


def _outermost(ops, starts, lo, hi):
    """The indices of the events of ``ops`` (sorted by start, longest first;
    ``starts`` theirs) inside ``[lo, hi]`` and inside no other such event."""
    out, end = [], float("-inf")
    for i in range(bisect.bisect_left(starts, lo), len(ops)):
        e = ops[i]
        if e.start >= hi:
            break
        if e.end > hi or e.end <= end:
            continue
        out.append(i)
        end = e.end
    return out


def stage_ms(ctx):
    """Device ms per search-program run, by stage and ``other``; None where
    the trace holds no run or names no stage."""
    if "stage_ms" not in ctx:
        ctx["stage_ms"] = _stage_ms(ctx)
    return ctx["stage_ms"]


def _stage_ms(ctx):
    tr = ctx["trace"]
    runs = tr.module_events(lambda n: MODULE in n)
    names = trace_op_names(ctx) if runs else None
    if not names:
        return None
    ops = sorted(tr.ops.get(tr.first_chip(), []), key=lambda e: (e.start, -e.end))
    starts = [e.start for e in ops]
    scope = [stage_of(names.get(e.name, "")) for e in ops]
    if not any(scope):
        return None
    total = {OTHER: 0.0}
    seen = unknown = 0
    inherited = 0.0
    for run in runs:
        prev = None
        for i in _outermost(ops, starts, run.start, run.end):
            e, stage = ops[i], scope[i]
            seen += 1
            unknown += e.name not in names
            if stage is None:
                j = i + 1
                while stage is None and j < len(ops) and ops[j].start < e.end:
                    stage = scope[j] if ops[j].end <= e.end else None
                    j += 1
                stage = stage or prev
                inherited += (e.end - e.start) if stage else 0.0
            prev = stage or prev
            total[stage or OTHER] = total.get(stage or OTHER, 0.0) + e.end - e.start
    ms = {s: v / len(runs) / 1e6 for s, v in total.items()}
    module_ms = sum(e.end - e.start for e in runs) / len(runs) / 1e6
    print(
        f"[stages] runs={len(runs)} events={seen} not_in_metadata={unknown} "
        f"module_ms={module_ms:.6f} staged_ms={sum(ms.values()) - ms[OTHER]:.6f} "
        f"inherited_ms={inherited / len(runs) / 1e6:.6f} "
        + " ".join(f"{s}={v:.6f}" for s, v in sorted(ms.items())),
        file=sys.stderr, flush=True,
    )
    return ms


def stage_reading(ctx, stage: str):
    ms = stage_ms(ctx)
    return None if ms is None else ms.get(stage, 0.0)


def facade_spans(ctx, name: str) -> list:
    """The program's process-wide spans called ``name`` that began inside
    the window (``ctx["facade_spans"]`` where a test gives them)."""
    spans = ctx.get("facade_spans")
    if spans is None:
        from repro.obs import get_tracer

        spans = get_tracer().spans()
    win = ctx["window"]
    return [s for s in spans if s.name == name and win.t0 <= s.ts <= win.t_end]
