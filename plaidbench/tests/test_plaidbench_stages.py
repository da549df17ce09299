"""The stage and serving readers: device time by pipeline stage, the
facade's launch spans, and the chip's idle time while requests were held.

Hand-made traces pin the arithmetic.  Two traces recorded on a TPU v5 lite
with ``record_trace.py`` (three ``plaid-pallas`` searches of the rehearsal
corpus at B = 4) pin the readers on real operations: ``stages.xplane.pb``
of the program with its stage scopes, ``small.xplane.pb`` of the program
before it had them.
"""
from __future__ import annotations

import os
import pathlib
import types

import numpy as np
import pytest

from plaidbench import spec, stages, xplane
from plaidbench.xplane import Event, TraceSummary
from repro.obs.trace import Span

REPO = pathlib.Path(__file__).resolve().parents[2]
DATA = pathlib.Path(__file__).resolve().parent / "data"
STAGE_METRICS = {
    "s1_device_ms.bulk": "plaid.s1",
    "cand_device_ms.bulk": "plaid.cand",
    "s2_gather_device_ms.bulk": "plaid.s2.gather",
    "s2_score_device_ms.bulk": "plaid.s2.score",
    "s3_device_ms.bulk": "plaid.s3",
    "s4_device_ms.bulk": "plaid.s4",
}


def _read(name, ctx):
    return spec.load_reader(REPO, name)(ctx)


def _window(t0, t_end, answered):
    return types.SimpleNamespace(t0=t0, t_end=t_end, answered=np.ones(answered, bool))


# ---- attribution by stage, by hand -----------------------------------------
def _op(name, a, b):
    return Event(f"%{name} = f32[8]{{0:T(1024)}} fusion(%x), kind=kLoop", a, b, {})


def _bulk_ctx(scoped=True):
    mod = [Event("jit_run_pipeline_impl(123)", 0.0, 100e6, {}),
           Event("jit_run_pipeline_impl(123)", 200e6, 300e6, {})]
    ops = []
    for base in (0.0, 200e6):
        ops += [
            _op("copy.0", base + 0, base + 5e6),            # first, unscoped: other
            _op("fusion.1", base + 5e6, base + 10e6),       # s1
            _op("while.2", base + 10e6, base + 50e6),       # unscoped: its body's
            _op("fusion.8", base + 20e6, base + 25e6),      # inside, unscoped
            _op("fusion.9", base + 25e6, base + 30e6),      # inside: cand
            _op("fusion.3", base + 50e6, base + 80e6),      # s4
            _op("copy.4", base + 80e6, base + 90e6),        # unscoped: the one before's
            _op("fusion.5", base + 90e6, base + 95e6),      # metadata names no stage
        ]
    ops.append(_op("fusion.1", 150e6, 160e6))  # between runs: not counted
    names = {
        "fusion.1": "jit(run_pipeline_impl)/plaid.s1/dot_general:",
        "fusion.9": "jit(run_pipeline_impl)/plaid.cand/plaid.s3/gather:",
        "fusion.3": "jit(run_pipeline_impl)/plaid.s4/jit(f)/reduce_max:",
        "fusion.5": "jit(run_pipeline_impl)/add:",
    }
    op_names = {e.name: "" for e in ops}
    if scoped:
        op_names.update({e.name: names[e.name.split()[0][1:]] for e in ops
                         if e.name.split()[0][1:] in names})
    tr = TraceSummary((0.0, 300e6), {0: ops}, {0: mod}, [])
    return {"trace": tr, "op_names": op_names}


def test_stage_time_counts_outermost_events_by_scope():
    ctx = _bulk_ctx()
    ms = stages.stage_ms(ctx)
    # while.2 takes the innermost scope of the first scoped event inside it
    assert ms == {"other": 5.0, "plaid.s1": 5.0, "plaid.s3": 40.0, "plaid.s4": 45.0}
    for name, stage in STAGE_METRICS.items():
        assert _read(name, ctx) == ms.get(stage, 0.0)


def test_stage_of_reads_the_innermost_plaid_scope():
    assert stages.stage_of("jit(run_pipeline_impl)/plaid.s2.gather/gather:") == "plaid.s2.gather"
    assert stages.stage_of("jit(f)/plaid.cand/vmap(jit(g))/plaid.s3/ne:") == "plaid.s3"
    assert stages.stage_of("jit(f)/plaid.s1:dot") == "plaid.s1"
    assert stages.stage_of("index.residuals:") is None
    assert stages.stage_of("") is None


def test_stage_readers_read_nothing_from_a_program_without_scopes():
    ctx = _bulk_ctx(scoped=False)
    assert all(_read(name, ctx) is None for name in STAGE_METRICS)
    ctx = _bulk_ctx()
    ctx["trace"].modules[0].clear()
    assert _read("s1_device_ms.bulk", ctx) is None
    # the chip trace of the program before the scopes
    path = DATA / "small.xplane.pb"
    ctx = {"trace": xplane.reduce(str(path)), "op_names": stages.op_names(path, "/device:TPU:0")}
    assert ctx["trace"].module_events(lambda n: stages.MODULE in n)
    assert all(_read(name, ctx) is None for name in STAGE_METRICS)


def test_the_newest_trace_is_read(tmp_path, monkeypatch):
    monkeypatch.setattr(stages, "TRACES", tmp_path)
    tr = TraceSummary((0.0, 1.0), {0: []}, {0: []}, [])
    assert stages.trace_op_names({"trace": tr}) is None
    for i, src in enumerate(("stages.xplane.pb", "small.xplane.pb")):
        dest = tmp_path / f"cell-{i}" / "run.xplane.pb"
        dest.parent.mkdir()
        dest.write_bytes((DATA / src).read_bytes())
        os.utime(dest, (1000 - i, 1000 - i))  # the first is the newest
    names = stages.trace_op_names({"trace": tr})
    assert any(stages.stage_of(v) for v in names.values())


# ---- held idle, by hand -----------------------------------------------------
OFFSET_NS = 5e9  # the trace's clock runs 5 s ahead of the program's


def _serving_ctx(jitter_ns=(0.0, 2e3, 1e3), drop_batch=False, extra_dispatch=False):
    # program clock (s): two batches; batch 0 holds requests 0 and 1, batch 1 request 2
    def span(name, ts, dur, **attrs):
        return Span(name, ts, dur, 1, attrs or None)

    b = (lambda i: {} if drop_batch else {"batch": i})
    serve = [
        span("serve.queue_wait", 10.000, 0.010, rid=0, **b(0)),
        span("serve.queue_wait", 10.005, 0.005, rid=1, **b(0)),
        span("serve.dispatch", 10.010, 0.050, batch=0, n=2),
        span("serve.queue_wait", 10.100, 0.020, rid=2, **b(1)),
        span("serve.dispatch", 10.120, 0.050, batch=1, n=1),
    ]
    facade = [span("retrieval.search_batch", 10.011, 0.048),
              span("retrieval.search_batch", 10.121, 0.048),
              span("retrieval.search_batch", 9.0, 0.01)]  # before the window
    ann = [Event("plaidbench.dispatch", f.ts * 1e9 + OFFSET_NS - 1e3 + j, 0, {})
           for f, j in zip(facade[:2], jitter_ns)]
    if extra_dispatch:
        ann.append(Event("plaidbench.dispatch", 10.3e9 + OFFSET_NS, 10.31e9 + OFFSET_NS, {}))
    # the chip is busy [10.020, 10.050] and [10.125, 10.165] (program clock)
    busy = [(10.020, 10.050), (10.125, 10.165)]
    ops = [Event("%fusion.1 = f32[1] fusion()", a * 1e9 + OFFSET_NS, c * 1e9 + OFFSET_NS, {})
           for a, c in busy]
    lo, hi = 9.9e9 + OFFSET_NS, 10.4e9 + OFFSET_NS
    tr = TraceSummary((lo, hi), {0: ops}, {0: []}, ann)
    return {"trace": tr, "spans": serve, "facade_spans": facade,
            "window": _window(9.95, 10.2, 3)}


def test_held_idle_is_idle_time_while_requests_wait_or_run():
    # held: [10.000, 10.060] and [10.100, 10.170]; busy inside: 30 + 40 ms
    got = _read("held_idle_ms.poisson", _serving_ctx())
    # the pairs sit 1 us either side of the offset: their median is it
    assert got == pytest.approx((60 - 30 + 70 - 40) / 3, abs=1e-5)


@pytest.mark.parametrize("case", ["count", "spread", "no_batch"])
def test_held_idle_refuses_a_join_it_cannot_trust(case):
    kw = {"count": dict(extra_dispatch=True), "spread": dict(jitter_ns=(0.0, 2e6)),
          "no_batch": dict(drop_batch=True)}[case]
    assert _read("held_idle_ms.poisson", _serving_ctx(**kw)) is None


def test_launch_reads_the_facade_spans_in_the_window():
    ctx = _serving_ctx()
    ctx["facade_spans"] = [Span("retrieval.launch", 10.011, 0.004, 1, None),
                           Span("retrieval.launch", 10.121, 0.002, 1, None),
                           Span("retrieval.launch", 9.0, 1.0, 1, None)]
    assert _read("launch_ms.poisson", ctx) == pytest.approx(3.0)
    ctx["facade_spans"] = []
    assert _read("launch_ms.poisson", ctx) is None


# ---- the chip trace of the program with its scopes ------------------------
@pytest.fixture(scope="module")
def recorded():
    path = DATA / "stages.xplane.pb"
    return xplane.reduce(str(path)), stages.op_names(path, "/device:TPU:0")


def test_op_names_read_from_a_chip_trace(recorded):
    tr, names = recorded
    # every operation the chip ran is in the metadata
    assert {e.name for e in tr.ops[0]} <= set(names)
    assert stages.op_names(DATA / "stages.xplane.pb", "/device:TPU:9") == {}
    by_instr = {n.split(" = ")[0]: v for n, v in names.items()}
    assert by_instr["%fusion.7"] == "jit(run_pipeline_impl)/plaid.s2.gather/gather:"
    assert by_instr["%while.11"] == ""  # the compiler's loop has no op_name
    # the Pallas kernels keep their names and their stages
    kernel = {n: stages.stage_of(v) for n, v in by_instr.items()
              if n.startswith(("%centroid_interaction_batched", "%decompress_and_score_batched"))}
    assert kernel == {"%centroid_interaction_batched.2": "plaid.s2.score",
                      "%centroid_interaction_batched.3": "plaid.s3",
                      "%decompress_and_score_batched.1": "plaid.s4"}


def test_stage_readers_on_a_chip_trace(recorded):
    tr, names = recorded
    ctx = {"trace": tr, "op_names": names}
    got = {stage: _read(name, ctx) for name, stage in STAGE_METRICS.items()}
    runs = tr.module_events(lambda n: stages.MODULE in n)
    assert len(runs) == 3
    module_ms = sum(e.end - e.start for e in runs) / len(runs) / 1e6
    assert module_ms == pytest.approx(14.761937, abs=1e-6)
    # the stages hold the whole run but the gaps between its operations
    assert sum(got.values()) >= 0.9999 * module_ms
    assert stages.stage_ms(ctx)[stages.OTHER] < 1e-4
    assert got == pytest.approx({
        "plaid.s1": 0.046746, "plaid.cand": 0.321135, "plaid.s2.gather": 4.310594,
        "plaid.s2.score": 8.914346, "plaid.s3": 0.999817, "plaid.s4": 0.168090,
    }, abs=1e-6)
