"""Observability: metrics registry, span tracer, funnel telemetry, and the
pallas_call <-> traffic-model completeness lint.

Covers the three obs pillars plus their compile-discipline guarantees:
the funnel aux must add ZERO retraces on t_cs sweeps and must not break
the stage-1 single-matmul HLO guard; the tracer must survive concurrent
writers and export valid Chrome trace-event JSON; the metrics bag must be
strict about counter names and batch LatencyWindow.extend under one lock.
"""
import json
import threading

import jax.numpy as jnp
import numpy as np
import pytest

from repro import retrieval
from repro.core import index as index_mod
from repro.core import pipeline, plaid
from repro.data import synthetic as syn
from repro.launch import hlo_analysis
from repro.obs.funnel import FunnelStats, merge, reduce_stacked
from repro.obs.metrics import (
    Counter,
    Counters,
    Gauge,
    LatencyWindow,
    MetricsRegistry,
)
from repro.obs.trace import Tracer
from repro.retrieval.types import RetrieverConfig, SearchParams


# --------------------------------------------------------------------------
# Metrics
# --------------------------------------------------------------------------
def test_counters_strict_by_default():
    """A name the bag was not constructed with is a typo, not a counter."""
    c = Counters("a", "b")
    c.inc("a")
    c.inc("b", 3)
    assert c["a"] == 1 and c["b"] == 3
    with pytest.raises(KeyError):
        c.inc("typo")
    with pytest.raises(KeyError):
        c["typo"]
    assert "typo" not in c.snapshot()


def test_counters_non_strict_keeps_legacy_behaviour():
    c = Counters(strict=False)
    c.inc("adhoc")
    assert c["adhoc"] == 1
    assert c["never_incremented"] == 0


def test_latency_window_extend_matches_add_loop():
    """extend() is semantically add() in a loop: same ring, same totals."""
    a, b = LatencyWindow(8), LatencyWindow(8)
    vals = [0.001 * i for i in range(20)]  # wraps the capacity-8 ring
    for v in vals:
        a.add(v)
    b.extend(vals)
    assert a.summary() == b.summary()
    assert a.count == b.count == 20


def test_latency_window_extend_single_lock_acquisition():
    """The satellite fix: a batch replay must take the lock once, not per
    element (asserted by counting acquisitions on a proxy lock)."""

    class CountingLock:
        def __init__(self):
            self.acquisitions = 0
            self._l = threading.Lock()

        def __enter__(self):
            self.acquisitions += 1
            return self._l.__enter__()

        def __exit__(self, *exc):
            return self._l.__exit__(*exc)

    w = LatencyWindow(16)
    lock = CountingLock()
    w._lock = lock
    w.extend([0.001] * 100)
    assert lock.acquisitions == 1
    w.extend([])  # empty batch: no lock traffic at all
    assert lock.acquisitions == 1


def test_registry_get_or_create_and_kind_mismatch():
    r = MetricsRegistry()
    assert r.counter("x") is r.counter("x")
    with pytest.raises(TypeError):
        r.gauge("x")


def test_registry_snapshot_and_prometheus_export():
    r = MetricsRegistry(namespace="repro")
    r.counter("reqs").inc(5)
    r.gauge("depth").set(3)
    r.window("w").add(0.01)
    snap = r.snapshot()
    assert snap["reqs"] == dict(type="counter", value=5)
    assert snap["depth"]["value"] == 3.0
    assert snap["w"]["n"] == 1
    json.dumps(snap)  # JSON-safe end to end
    text = r.to_prometheus()
    assert "# TYPE repro_reqs counter" in text
    assert "repro_reqs 5" in text


def test_serving_stats_shim_reexports():
    """serving.stats stays importable (compat shim over obs.metrics)."""
    from repro.serving import stats as shim

    assert shim.Counters is Counters
    assert shim.LatencyWindow is LatencyWindow


# --------------------------------------------------------------------------
# Tracer
# --------------------------------------------------------------------------
def test_tracer_deterministic_with_fake_clock():
    t = [0.0]

    def clock():
        t[0] += 0.5
        return t[0]

    tr = Tracer(clock=clock)
    with tr.span("a", foo=1):
        pass
    (s,) = tr.spans("a")
    assert s.ts == 0.5 and s.dur == 0.5 and s.attrs == {"foo": 1}
    assert tr.durations_ms("a") == [500.0]


def test_tracer_records_span_on_exception():
    tr = Tracer()
    with pytest.raises(RuntimeError):
        with tr.span("boom"):
            raise RuntimeError("x")
    assert len(tr.spans("boom")) == 1


def test_tracer_ring_bounds_memory():
    tr = Tracer(capacity=16)
    for i in range(100):
        tr.instant("tick", i=i)
    spans = tr.spans()
    assert len(spans) == 16
    assert spans[-1].attrs == {"i": 99}  # newest kept, oldest dropped


def test_tracer_concurrent_writers_race_free():
    """N threads hammer one tracer; every record lands, nothing raises."""
    tr = Tracer(capacity=100_000)
    n_threads, per = 8, 500
    errors = []

    def work(tid):
        try:
            for i in range(per):
                with tr.span("w", tid=tid, i=i):
                    pass
        except Exception as e:  # pragma: no cover - the failure mode
            errors.append(e)

    threads = [
        threading.Thread(target=work, args=(t,)) for t in range(n_threads)
    ]
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    assert not errors
    assert len(tr.spans("w")) == n_threads * per
    # per-thread monotonicity survives interleaving
    by_tid = {}
    for s in tr.spans("w"):
        by_tid.setdefault(s.attrs["tid"], []).append(s.ts)
    for ts in by_tid.values():
        assert ts == sorted(ts)


def test_chrome_trace_export_round_trips(tmp_path):
    """export() -> json.loads gives spec-valid events: complete spans carry
    ph='X' with microsecond ts/dur, instants ph='i' with scope 't'."""
    t = [0.0]

    def clock():
        t[0] += 0.25
        return t[0]

    tr = Tracer(clock=clock)
    with tr.span("dispatch", bucket=4):
        pass
    tr.instant("generation_bump", generation=3)
    path = tmp_path / "trace.json"
    n = tr.export(str(path))
    assert n == 2
    payload = json.loads(path.read_text())
    assert payload["displayTimeUnit"] == "ms"
    events = payload["traceEvents"]
    assert [e["name"] for e in events] == ["dispatch", "generation_bump"]
    full, instant = events
    assert full["ph"] == "X"
    assert full["ts"] == pytest.approx(0.25e6)
    assert full["dur"] == pytest.approx(0.25e6)
    assert full["args"] == {"bucket": 4}
    assert instant["ph"] == "i" and instant["s"] == "t"
    for e in events:
        assert isinstance(e["ts"], float) and isinstance(e["dur"], float)
        assert isinstance(e["pid"], int) and isinstance(e["tid"], int)


def test_span_ids_and_parents_across_tracers():
    """Every span gets a process-unique sid; its parent is the innermost
    span open on its thread, whichever tracer holds that span."""
    a, b = Tracer(), Tracer()
    with a.span("outer"):
        with b.span("inner"):
            a.instant("tick")
        b.record("late", 0.0, 1.0)
    with b.span("top"):
        pass
    (outer,), (inner,), (tick,) = a.spans("outer"), b.spans("inner"), a.spans("tick")
    (late,), (top,) = b.spans("late"), b.spans("top")
    sids = [s.sid for s in (outer, inner, tick, late, top)]
    assert len(set(sids)) == 5 and all(isinstance(i, int) for i in sids)
    assert outer.parent is None and top.parent is None
    assert inner.parent == outer.sid
    assert tick.parent == inner.sid
    assert late.parent == outer.sid
    # another thread starts with nothing open
    def work():
        with a.span("other"):
            pass

    with a.span("held"):
        th = threading.Thread(target=work)
        th.start()
        th.join(timeout=30)
    assert not th.is_alive()
    assert a.spans("other")[0].parent is None


def test_span_enters_profiler_annotation(monkeypatch):
    """A live span is also a jax.profiler.TraceAnnotation under its name and
    attributes, open strictly inside the tracer's clock readings; a
    retroactive one is not."""
    from repro.obs import trace as trace_mod

    t = [0.0]
    events = []

    def clock():
        t[0] += 1.0
        events.append(("clock", t[0]))
        return t[0]

    class FakeAnnotation:
        def __init__(self, name, **kw):
            self.name, self.kw = name, kw

        def __enter__(self):
            events.append(("enter", self.name, self.kw))

        def __exit__(self, *exc):
            events.append(("exit", self.name))

    monkeypatch.setattr(trace_mod._profiler, "TraceAnnotation", FakeAnnotation)
    tr = Tracer(clock=clock)
    with tr.span("serve.dispatch", batch=7, bucket=4):
        events.append(("body",))
    tr.record("serve.queue_wait", 0.5, 0.25, rid=3, batch=7)
    assert events == [
        ("clock", 1.0),
        ("enter", "serve.dispatch", {"batch": 7, "bucket": 4}),
        ("body",),
        ("exit", "serve.dispatch"),
        ("clock", 2.0),
    ]
    d, q = tr.spans()
    assert (d.ts, d.dur, d.attrs) == (1.0, 1.0, {"batch": 7, "bucket": 4})
    assert (q.ts, q.dur, q.attrs) == (0.5, 0.25, {"rid": 3, "batch": 7})


def test_chrome_trace_export_carries_ids_and_parents():
    t = [0.0]

    def clock():
        t[0] += 0.5
        return t[0]

    tr = Tracer(clock=clock)
    with tr.span("a"):
        with tr.span("b", n=2):
            pass
    events = {e["name"]: e for e in json.loads(json.dumps(tr.to_chrome_trace()))["traceEvents"]}
    a, b = tr.spans("a")[0], tr.spans("b")[0]
    assert events["a"]["id"] == a.sid and "args" not in events["a"]
    assert events["b"]["id"] == b.sid
    assert events["b"]["args"] == {"n": 2, "parent": a.sid}


def test_tracer_summary_rollup():
    t = [0.0]

    def clock():
        t[0] += 1.0
        return t[0]

    tr = Tracer(clock=clock)
    for _ in range(3):
        with tr.span("x"):
            pass
    s = tr.summary()["x"]
    assert s["count"] == 3
    assert s["mean_ms"] == pytest.approx(1000.0)


# --------------------------------------------------------------------------
# Funnel telemetry
# --------------------------------------------------------------------------
@pytest.fixture(scope="module")
def funnel_index():
    docs, _ = syn.embedding_corpus(120, dim=16, min_len=6, max_len=12, seed=3)
    idx = index_mod.build_index(docs, num_centroids=16, nbits=2, kmeans_iters=3)
    qs, _ = syn.queries_from_docs(docs, 6, q_len=4)
    return docs, idx, jnp.asarray(qs)


def _params():
    return plaid.SearchParams(k=5, nprobe=2, ndocs=32, candidate_cap=64)


def test_funnel_values_consistent_with_diag(funnel_index):
    """The funnel's shared fields agree exactly with the diag counters, and
    every count respects the funnel's monotone narrowing."""
    _, idx, qs = funnel_index
    masks = jnp.ones(qs.shape[:2], jnp.float32)
    p = _params()
    out = pipeline.run_pipeline(
        idx, qs, masks, 0.4, p, diag=True, funnel=True
    )
    scores, pids, diag, fs = out
    assert isinstance(fs, FunnelStats)
    np.testing.assert_array_equal(
        np.asarray(fs.stage1_candidates), np.asarray(diag["stage1_candidates"])
    )
    np.testing.assert_array_equal(
        np.asarray(fs.stage2_kept_centroids),
        np.asarray(diag["stage2_kept_centroids"]),
    )
    np.testing.assert_array_equal(
        np.asarray(fs.stage3_survivors), np.asarray(diag["stage3_survivors"])
    )
    s1 = np.asarray(fs.stage1_candidates)
    s2 = np.asarray(fs.stage2_survivors)
    s3 = np.asarray(fs.stage3_survivors)
    assert (s2 <= s1).all() and (s3 <= s2).all()  # the funnel narrows
    assert (np.asarray(fs.probed_centroids) <= idx.num_centroids).all()
    assert (np.asarray(fs.alive_dropped) == 0).all()  # no tombstones here
    assert (np.asarray(fs.gathered_tokens) > 0).all()


def test_funnel_zero_retrace_on_t_cs_sweep(funnel_index):
    """Compile discipline: with funnel ON, a t_cs sweep still retraces
    zero times (the funnel is a static flag, not a traced shape)."""
    _, idx, qs = funnel_index
    masks = jnp.ones(qs.shape[:2], jnp.float32)
    p = _params()
    pipeline.run_pipeline(idx, qs, masks, 0.5, p, funnel=True)  # warm
    n0 = plaid.trace_count()
    for t in (0.3, 0.45, 0.6):
        out = pipeline.run_pipeline(idx, qs, masks, t, p, funnel=True)
        assert len(out) == 3
    assert plaid.trace_count() == n0, "funnel aux must not retrace on sweeps"


def test_funnel_on_keeps_single_stage1_dot(funnel_index):
    """The HLO guard holds with instrumentation enabled: funnel reductions
    reuse the one batchwide stage-1 C.Q^T dot (CSE), they do not add one."""
    _, idx, qs = funnel_index
    K, (B, nq, _) = idx.num_centroids, qs.shape
    p = _params()
    lowered = pipeline.run_pipeline_jit.lower(
        idx, qs, jnp.ones((B, nq), jnp.float32), jnp.float32(0.4),
        params=p, funnel=True,
    )
    hlo = lowered.compile().as_text()
    comps = hlo_analysis.parse_module(hlo)
    exec_mult, _ = hlo_analysis._multipliers(comps)
    stage1 = []
    for cname, comp in comps.items():
        for ins in comp.instrs:
            if ins.op != "dot":
                continue
            dims = hlo_analysis._shape_dims(ins.rtype)
            n = int(np.prod(dims)) if dims else 0
            if n == K * B * nq and K in dims:
                stage1.append((cname, ins, exec_mult.get(cname) or 1.0))
    assert len(stage1) == 1, [s[1].raw for s in stage1]
    assert stage1[0][2] == 1.0


def test_funnel_merge_semantics():
    """Doc-partitioned counts ADD, centroid-replicated counts MAX."""

    def fs(probed, s1):
        return FunnelStats(
            probed_centroids=jnp.asarray([probed], jnp.int32),
            stage1_candidates=jnp.asarray([s1], jnp.int32),
            alive_dropped=jnp.asarray([1], jnp.int32),
            stage2_kept_centroids=jnp.asarray([7], jnp.int32),
            stage2_survivors=jnp.asarray([s1 // 2], jnp.int32),
            stage3_survivors=jnp.asarray([s1 // 4], jnp.int32),
            gathered_tokens=jnp.asarray([s1 * 3], jnp.int32),
        )

    m = merge([fs(5, 20), fs(5, 12)])
    assert int(m.stage1_candidates[0]) == 32  # additive: partitioned docs
    assert int(m.gathered_tokens[0]) == 96
    assert int(m.alive_dropped[0]) == 2
    assert int(m.probed_centroids[0]) == 5  # replicated: max, not sum
    assert int(m.stage2_kept_centroids[0]) == 7
    stacked = FunnelStats(*(jnp.stack([a, b]) for a, b in zip(fs(5, 20), fs(5, 12))))
    r = reduce_stacked(stacked)
    for field in FunnelStats._fields:
        np.testing.assert_array_equal(
            np.asarray(getattr(r, field)), np.asarray(getattr(m, field))
        )


def test_funnel_alive_dropped_counts_tombstoned_candidates(funnel_index):
    """Tombstoning docs surfaces in alive_dropped and shrinks the funnel."""
    _, idx, qs = funnel_index
    masks = jnp.ones(qs.shape[:2], jnp.float32)
    p = _params()
    alive = np.ones(idx.num_passages, bool)
    alive[::3] = False  # kill a third of the corpus
    _, _, fs_dead = pipeline.run_pipeline(
        idx, qs, masks, 0.4, p, funnel=True, alive=jnp.asarray(alive)
    )
    _, _, fs_all = pipeline.run_pipeline(idx, qs, masks, 0.4, p, funnel=True)
    assert (np.asarray(fs_dead.alive_dropped) > 0).any()
    assert (
        np.asarray(fs_dead.stage1_candidates)
        <= np.asarray(fs_all.stage1_candidates)
    ).all()


def test_funnel_agrees_across_backends(funnel_index):
    """The merge layers are invisible: plaid (one partition), live (stacked
    segments) and live-sharded (shard_map base) report the SAME funnel for
    the same corpus and params."""
    docs, _, qs = funnel_index
    cfg = RetrieverConfig(
        params=SearchParams(k=5, nprobe=2, ndocs=32, candidate_cap=64),
        index=dict(num_centroids=16, nbits=2, kmeans_iters=3, seed=0),
        n_shards=1,
    )
    funnels = {}
    for backend in ("plaid", "live", "live-sharded"):
        r = retrieval.build(docs, cfg.replace(backend=backend))
        res = r.search_batch(qs, with_funnel=True)
        assert res.funnel is not None
        funnels[backend] = res.funnel
        assert r.search_batch(qs).funnel is None  # opt-in only
    ref = funnels["plaid"]
    for backend in ("live", "live-sharded"):
        for field, v in funnels[backend].items():
            np.testing.assert_array_equal(
                np.asarray(v), np.asarray(ref[field]), err_msg=f"{backend}/{field}"
            )


def test_funnel_rejected_on_vanilla(funnel_index):
    docs, _, qs = funnel_index
    cfg = RetrieverConfig(
        backend="vanilla",
        params=SearchParams(k=5, nprobe=2, ndocs=32, candidate_cap=64),
        index=dict(num_centroids=16, nbits=2, kmeans_iters=3, seed=0),
    )
    r = retrieval.build(docs, cfg)
    with pytest.raises(ValueError, match="with_funnel"):
        r.search_batch(qs, with_funnel=True)


def test_funnel_single_query_squeeze(funnel_index):
    docs, _, qs = funnel_index
    cfg = RetrieverConfig(
        params=SearchParams(k=5, nprobe=2, ndocs=32, candidate_cap=64),
        index=dict(num_centroids=16, nbits=2, kmeans_iters=3, seed=0),
    )
    r = retrieval.build(docs, cfg)
    batched = r.search_batch(qs, with_funnel=True).funnel
    single = r.search(qs[0], with_funnel=True).funnel
    for field, v in single.items():
        assert np.ndim(v) == 0
        assert int(v) == int(np.asarray(batched[field])[0])


# --------------------------------------------------------------------------
# Completeness lint: every pallas_call has a traffic record
# --------------------------------------------------------------------------
def test_every_pallas_call_site_has_a_cost_record():
    """AST-scan repro.kernels for pallas_call-launching functions; each must
    appear in costs.KERNEL_COSTS or (with a reason) costs.UNMODELED_KERNELS.
    A kernel outside the traffic model is a kernel bench_diff cannot gate."""
    import ast
    import pathlib

    import repro.kernels as kernels_pkg
    from repro.kernels import costs

    kdir = pathlib.Path(kernels_pkg.__file__).parent
    sites: dict[str, list[str]] = {}
    for py in sorted(kdir.glob("*.py")):
        tree = ast.parse(py.read_text())
        for node in ast.walk(tree):
            if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue
            calls = [
                sub
                for sub in ast.walk(node)
                if isinstance(sub, ast.Attribute) and sub.attr == "pallas_call"
            ]
            if calls:
                sites.setdefault(node.name, []).append(py.name)
    assert sites, "no pallas_call sites found — scan is broken"

    covered = set(costs.KERNEL_COSTS) | set(costs.UNMODELED_KERNELS)
    missing = {n: f for n, f in sites.items() if n not in covered}
    assert not missing, (
        f"pallas_call sites without a kernels/costs.py traffic record: "
        f"{missing}; add a cost fn to KERNEL_COSTS or an explicit reasoned "
        "exemption to UNMODELED_KERNELS"
    )
    # the registry must not rot either: every entry points at a real site
    stale = covered - set(sites)
    assert not stale, f"costs.py registry names without a pallas_call site: {stale}"
    # exemptions carry human-readable reasons
    for name, reason in costs.UNMODELED_KERNELS.items():
        assert isinstance(reason, str) and len(reason) > 10, name


def test_registered_cost_fns_return_gateable_records():
    """Every KERNEL_COSTS entry produces the hbm_bytes/flops dict shape
    bench_diff gates on, with positive traffic."""
    from repro.kernels import costs

    geom = dict(B=2, L=16, pd=4, K=32, d=16, nq=4, nbits=2)
    calls = {
        costs.centroid_interaction_batched_cost: dict(
            B=2, nd=64, L=16, K=32, nq=4
        ),
        costs.decompress_residuals_cost: dict(n=128, pd=4, nbits=2),
        costs.decompress_and_score_batched_cost: dict(nd=64, **geom),
        costs.gather_decompress_maxsim_cost: dict(n3=16, **geom),
    }
    seen = set()
    for name, fn in costs.KERNEL_COSTS.items():
        if fn in seen:
            continue
        seen.add(fn)
        rec = fn(**calls[fn])
        assert set(rec) == {"hbm_bytes", "flops"}, name
        assert rec["hbm_bytes"] > 0, name
