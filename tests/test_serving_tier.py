"""Serving tier: bucketed dispatch, per-request knobs, admission control,
generation-aware result cache, replicas, and the serving stress test.

Timing-sensitive behaviours (admission, deadlines, shutdown) are driven
through gated stub retrievers so every test is deterministic; compile
discipline and result correctness run against the real live backend.
"""
import queue as queue_mod
import threading
import time

import numpy as np
import pytest

from repro.core import pipeline
from repro.data import synthetic as syn
from repro import retrieval
from repro.retrieval import SearchParams, SearchRequest
from repro.serving import (
    AdmissionQueue,
    BatchingServer,
    DeadlineExceeded,
    LatencyWindow,
    QueueFull,
    ReplicaPool,
    ResultCache,
    ServerClosed,
    bucket_batch_size,
    bucket_ladder,
)
from repro.serving.buckets import pad_batch
from repro.serving.server import _Pending, ResultFuture

DIM = 32


# ---------------------------------------------------------------------------
# stubs: deterministic control over dispatch timing and failures
# ---------------------------------------------------------------------------
class StubRetriever:
    """A retriever whose dispatch the test can gate, fail, and observe."""

    backend_name = "stub"

    def __init__(self, k=4, gated=False):
        self.params = SearchParams(k=k)
        self.fail_with = None
        self.calls = []  # (batch_size, t_cs vector copy, first-lane marker)
        self.entered = threading.Event()  # set when a dispatch starts
        self.gate = threading.Event()  # dispatch blocks until set
        if not gated:
            self.gate.set()

    def search_batch(self, qs, t_cs=None):
        self.entered.set()
        self.gate.wait(timeout=30)
        if self.fail_with is not None:
            raise self.fail_with
        qs = np.asarray(qs)
        B, k = qs.shape[0], self.params.k
        ts = None if t_cs is None else np.asarray(t_cs).copy()
        self.calls.append((B, ts, float(qs[0, 0, 0])))
        scores = np.tile(np.arange(k, 0, -1, np.float32), (B, 1))
        # pids encode the query so result->request routing is checkable
        pids = (qs[:, :1, :1].reshape(B, 1) + np.arange(k)).astype(np.int32)
        return scores, pids


def _stub_query(marker: float) -> np.ndarray:
    q = np.zeros((4, DIM), np.float32)
    q[:, 0] = marker
    return q


def _wait(predicate, timeout=10.0, msg="condition"):
    t0 = time.perf_counter()
    while not predicate():
        if time.perf_counter() - t0 > timeout:
            raise AssertionError(f"timed out waiting for {msg}")
        time.sleep(0.002)


# ---------------------------------------------------------------------------
# fixtures: a real mutable corpus served end to end
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def live_setup():
    docs, _ = syn.embedding_corpus(150, dim=DIM, seed=0)
    r = retrieval.build(
        docs,
        backend="live",
        params=SearchParams(k=5, nprobe=4, t_cs=0.4),
        index=dict(num_centroids=32, kmeans_iters=3),
    )
    qs, _ = syn.queries_from_docs(docs, 8)
    return r, np.asarray(qs)


# ---------------------------------------------------------------------------
# buckets
# ---------------------------------------------------------------------------
def test_bucket_batch_size_pow2_rounding():
    assert [bucket_batch_size(n, 16) for n in (1, 2, 3, 4, 5, 9, 16)] == [
        1, 2, 4, 4, 8, 16, 16,
    ]
    # max_batch_size is a terminal bucket even when not a power of two
    assert bucket_batch_size(11, 12) == 12
    with pytest.raises(ValueError):
        bucket_batch_size(0, 16)
    with pytest.raises(ValueError):
        bucket_batch_size(17, 16)


def test_bucket_ladder():
    assert bucket_ladder(16) == (1, 2, 4, 8, 16)
    assert bucket_ladder(12) == (1, 2, 4, 8, 12)
    assert bucket_ladder(1) == (1,)


def test_pad_batch_replicates_last_lane():
    qs = [np.full((2, 3), i, np.float32) for i in range(3)]
    stacked, ts = pad_batch(qs, [0.1, 0.2, 0.3], 4)
    assert stacked.shape == (4, 2, 3) and ts.shape == (4,)
    np.testing.assert_array_equal(stacked[3], stacked[2])
    assert ts[3] == np.float32(0.3)


# ---------------------------------------------------------------------------
# bucketed dispatch + compile discipline (real backend)
# ---------------------------------------------------------------------------
def _pending(q, t_cs, k):
    return _Pending(
        q=q, t_cs=t_cs, k=k, t0=time.perf_counter(), deadline=None,
        future=ResultFuture(), cache_key=None,
    )


def test_bucketed_dispatch_results_match_direct_search(live_setup):
    r, qs = live_setup
    srv = BatchingServer(r, batch_size=8, max_wait_ms=2.0, cache_size=None)
    try:
        # exact bucket control: hand _dispatch coalesced batches directly
        for n, want_bucket in ((1, 1), (3, 4), (5, 8)):
            batch = [_pending(qs[i], 0.4, 5) for i in range(n)]
            srv._dispatch(batch)
            for i, p in enumerate(batch):
                res = p.future.get(timeout=10)
                direct = r.search(qs[i], t_cs=0.4)
                np.testing.assert_array_equal(res.pids, direct.pids)
        st = srv.stats()
        assert st["buckets"] == {1: 1, 4: 1, 8: 1}
        # a burst submitted through the public queue coalesces too
        futs = [srv.submit(qs[i]) for i in range(6)]
        for f in futs:
            assert f.get(timeout=30).pids.shape == (5,)
        assert sum(srv.stats()["buckets"].values()) > 3
    finally:
        srv.shutdown()


def test_zero_retrace_across_bucket_reuse_and_knob_variation(live_setup):
    r, qs = live_setup
    srv = BatchingServer(r, batch_size=8, max_wait_ms=2.0, cache_size=None)
    try:
        # warm each bucket once
        for n in (1, 2, 4):
            srv._dispatch([_pending(qs[i], 0.4, 5) for i in range(n)])
        warm_traces = pipeline.trace_count()
        # reuse every bucket across a grid of per-request t_cs and k:
        # traced thresholds + max-k truncation must hit the warm programs
        for n in (1, 2, 4):
            for t in (0.2, 0.45, 0.7):
                for k in (1, 3, 5):
                    batch = [
                        _pending(qs[i], t + 0.01 * i, k) for i in range(n)
                    ]
                    srv._dispatch(batch)
                    for p in batch:
                        assert p.future.get(timeout=10).pids.shape == (k,)
        assert pipeline.trace_count() == warm_traces
        srv.assert_zero_retrace()
    finally:
        srv.shutdown()


def test_per_request_t_cs_matches_per_request_direct_search(live_setup):
    r, qs = live_setup
    srv = BatchingServer(r, batch_size=8, max_wait_ms=2.0, cache_size=None)
    try:
        # one coalesced batch, three different thresholds
        knobs = [(0.2, 5), (0.5, 3), (0.8, 1)]
        batch = [_pending(qs[i], t, k) for i, (t, k) in enumerate(knobs)]
        srv._dispatch(batch)
        for i, (t, k) in enumerate(knobs):
            res = batch[i].future.get(timeout=10)
            direct = r.search(qs[i], t_cs=t)
            assert res.k == k and res.t_cs == t
            np.testing.assert_array_equal(res.pids, direct.pids[:k])
            np.testing.assert_allclose(res.scores, direct.scores[:k])
    finally:
        srv.shutdown()


def test_per_request_k_validation():
    srv = BatchingServer(StubRetriever(k=4), batch_size=2, max_wait_ms=0.5)
    try:
        with pytest.raises(ValueError, match="exceeds the compiled"):
            srv.submit(_stub_query(1.0), k=5)
        with pytest.raises(ValueError, match="k must be >= 1"):
            srv.submit(_stub_query(1.0), k=0)
        assert srv.search(_stub_query(1.0), k=2).pids.shape == (2,)
    finally:
        srv.shutdown()


def test_search_request_carries_serving_knobs():
    stub = StubRetriever(k=4)
    srv = BatchingServer(stub, batch_size=2, max_wait_ms=0.5, cache_size=None)
    try:
        req = SearchRequest(q=_stub_query(7.0), t_cs=0.9, k=2)
        res = srv.submit(req).get(timeout=10)
        assert res.t_cs == 0.9 and res.k == 2
        assert res.pids.shape == (2,)
        _, ts, marker = stub.calls[-1]
        assert marker == 7.0 and np.float32(0.9) in ts
    finally:
        srv.shutdown()


# ---------------------------------------------------------------------------
# admission control
# ---------------------------------------------------------------------------
def test_admission_queue_priority_order_and_drain():
    q = AdmissionQueue(max_pending=8)
    a, b, c = (_pending(_stub_query(i), 0.0, 1) for i in (1, 2, 3))
    q.put(a, "batch")
    q.put(b, "interactive")
    q.put(c, "batch")
    assert q.get(timeout=0) is b  # interactive pops first
    assert q.get(timeout=0) is a
    q.put(b, "interactive")
    assert [len(q)] == [2]
    assert q.drain() == [b, c]  # dispatch order: interactive first
    assert len(q) == 0
    with pytest.raises(ValueError, match="priority"):
        q.put(a, "bulk")


def test_queue_full_sheds_typed():
    stub = StubRetriever(gated=True)
    srv = BatchingServer(
        stub, batch_size=1, max_wait_ms=0.0, max_pending=2, cache_size=None
    )
    try:
        f0 = srv.submit(_stub_query(0.0))  # enters dispatch, blocks on gate
        _wait(stub.entered.is_set, msg="dispatcher pickup")
        f1 = srv.submit(_stub_query(1.0), priority="batch")
        f2 = srv.submit(_stub_query(2.0), priority="batch")  # queue now full
        # batch arrival beyond the bound is rejected outright
        with pytest.raises(QueueFull):
            srv.submit(_stub_query(3.0), priority="batch")
        # interactive arrival sheds the YOUNGEST queued batch request
        f4 = srv.submit(_stub_query(4.0))
        with pytest.raises(QueueFull):
            f2.get(timeout=10)
        # interactive arrival with no batch victim is rejected itself
        f5 = srv.submit(_stub_query(5.0))  # sheds f1
        with pytest.raises(QueueFull):
            srv.submit(_stub_query(6.0))
        assert srv._q.shed == 2 and srv._q.rejected == 2
        stub.gate.set()
        # survivors complete, routed to the right requests
        for f, marker in ((f0, 0.0), (f4, 4.0), (f5, 5.0)):
            assert f.get(timeout=10).pids[0] == int(marker)
        st = srv.stats()
        assert st["shed"] == 2 and st["rejected"] == 2
    finally:
        srv.shutdown()


def test_interactive_dispatches_ahead_of_batch():
    stub = StubRetriever(gated=True)
    srv = BatchingServer(stub, batch_size=1, max_wait_ms=0.0, cache_size=None)
    try:
        srv.submit(_stub_query(0.0))
        _wait(stub.entered.is_set, msg="dispatcher pickup")
        srv.submit(_stub_query(1.0), priority="batch")
        srv.submit(_stub_query(2.0), priority="interactive")
        stub.gate.set()
        _wait(lambda: len(stub.calls) == 3, msg="all dispatches")
        assert [c[2] for c in stub.calls] == [0.0, 2.0, 1.0]
    finally:
        srv.shutdown()


def test_expired_requests_skip_dispatch():
    stub = StubRetriever(gated=True)
    srv = BatchingServer(stub, batch_size=1, max_wait_ms=0.0, cache_size=None)
    try:
        srv.submit(_stub_query(0.0))
        _wait(stub.entered.is_set, msg="dispatcher pickup")
        f = srv.submit(_stub_query(1.0), timeout_ms=10.0)
        time.sleep(0.05)  # let the deadline lapse while queued
        stub.gate.set()
        with pytest.raises(DeadlineExceeded):
            f.get(timeout=10)
        _wait(lambda: srv.stats().get("expired") == 1, msg="expired counter")
        # the expired request never reached the retriever
        assert [c[2] for c in stub.calls] == [0.0]
    finally:
        srv.shutdown()


# ---------------------------------------------------------------------------
# satellite: dispatcher failures propagate, dispatcher survives
# ---------------------------------------------------------------------------
def test_dispatch_exception_propagates_and_dispatcher_survives():
    stub = StubRetriever()
    srv = BatchingServer(stub, batch_size=4, max_wait_ms=0.5, cache_size=None)
    try:
        stub.fail_with = RuntimeError("device OOM")
        with pytest.raises(RuntimeError, match="device OOM"):
            srv.submit(_stub_query(1.0)).get(timeout=10)
        # the dispatcher must still be alive and serving
        stub.fail_with = None
        res = srv.search(_stub_query(2.0), timeout=10)
        assert res.pids[0] == 2
        st = srv.stats()
        assert st["errors"] == 1 and st["completed"] == 1
    finally:
        srv.shutdown()


# ---------------------------------------------------------------------------
# satellite: bounded latency window
# ---------------------------------------------------------------------------
def test_spans_link_each_request_to_its_batch():
    """Every answered request's ``serve.queue_wait`` names its ``rid`` and
    exactly one ``batch``, whose ``serve.pad`` / ``serve.dispatch`` /
    ``serve.truncate`` carry the same number and begin after the wait
    ends."""
    from repro.obs.trace import Tracer

    stub = StubRetriever(gated=True)
    tracer = Tracer()
    srv = BatchingServer(
        stub, batch_size=4, max_wait_ms=1.0, cache_size=None, tracer=tracer
    )
    try:
        futs = [srv.submit(_stub_query(1.0))]
        _wait(stub.entered.is_set, msg="first dispatch")
        futs += [srv.submit(_stub_query(2.0 + i)) for i in range(9)]
        stub.gate.set()
        for f in futs:
            f.get(timeout=30)
    finally:
        srv.shutdown()
    waits = tracer.spans("serve.queue_wait")
    assert sorted(w.attrs["rid"] for w in waits) == list(range(10))
    assert len({w.attrs["batch"] for w in waits}) >= 3  # 1, then 4 + 4 + 1
    for name in ("serve.pad", "serve.dispatch", "serve.truncate"):
        by_batch = [s.attrs["batch"] for s in tracer.spans(name)]
        assert sorted(by_batch) == sorted({w.attrs["batch"] for w in waits})
    dispatch = {s.attrs["batch"]: s for s in tracer.spans("serve.dispatch")}
    for w in waits:
        d = dispatch[w.attrs["batch"]]
        assert w.ts + w.dur <= d.ts
        assert sum(1 for v in waits if v.attrs["batch"] == w.attrs["batch"]) == d.attrs["n"]


def test_latency_window_bounded_and_exact():
    w = LatencyWindow(capacity=4)
    assert w.summary() == {}
    for v in (1.0, 2.0, 3.0, 4.0, 5.0, 6.0):  # first two rotate out
        w.add(v)
    s = w.summary()
    assert s["n"] == 6 and s["window"] == 4
    assert s["p50_ms"] == pytest.approx(4.5e3)  # exact over [3,4,5,6]
    assert s["mean_ms"] == pytest.approx(3.5e3)  # all-time mean
    with pytest.raises(ValueError):
        LatencyWindow(capacity=0)


def test_server_latency_window_is_bounded():
    srv = BatchingServer(
        StubRetriever(), batch_size=1, max_wait_ms=0.0,
        cache_size=None, latency_window=8,
    )
    try:
        for i in range(20):
            srv.search(_stub_query(float(i)), timeout=10)
        st = srv.stats()
        assert st["n"] == 20 and st["window"] == 8
        assert srv._latencies._buf.shape == (8,)
    finally:
        srv.shutdown()


# ---------------------------------------------------------------------------
# satellite: graceful shutdown
# ---------------------------------------------------------------------------
def test_shutdown_drain_completes_queued_requests():
    stub = StubRetriever(gated=True)
    srv = BatchingServer(stub, batch_size=2, max_wait_ms=0.0, cache_size=None)
    futs = [srv.submit(_stub_query(float(i))) for i in range(5)]
    _wait(stub.entered.is_set, msg="dispatcher pickup")

    def release():
        time.sleep(0.05)
        stub.gate.set()

    t = threading.Thread(target=release)
    t.start()
    srv.shutdown(drain=True)
    t.join()
    for i, f in enumerate(futs):
        assert f.get(timeout=1).pids[0] == i  # all served before exit
    with pytest.raises(ServerClosed):
        srv.submit(_stub_query(9.0))


def test_shutdown_without_drain_fails_queued_waiters_typed():
    stub = StubRetriever(gated=True)
    srv = BatchingServer(stub, batch_size=1, max_wait_ms=0.0, cache_size=None)
    f0 = srv.submit(_stub_query(0.0))
    _wait(stub.entered.is_set, msg="dispatcher pickup")
    queued = [srv.submit(_stub_query(float(i))) for i in (1, 2, 3)]
    stub.gate.set()
    srv.shutdown(drain=False)
    assert f0.get(timeout=1).pids[0] == 0  # in-flight request still lands
    outcomes = []
    for f in queued:
        try:
            f.get(timeout=1)
            outcomes.append("served")
        except ServerClosed:
            outcomes.append("closed")
    assert "closed" in outcomes  # nobody hangs, queued work fails typed
    with pytest.raises(ServerClosed):
        srv.submit(_stub_query(9.0))


def test_submit_after_shutdown_raises_even_on_cache_hit():
    stub = StubRetriever()
    srv = BatchingServer(stub, batch_size=1, max_wait_ms=0.0, cache_size=32)
    q = _stub_query(1.0)
    srv.search(q, timeout=10)  # warm the cache
    assert srv.search(q, timeout=10).cached
    srv.shutdown()
    with pytest.raises(ServerClosed):  # the cache must not serve a
        srv.submit(q)  # closed server


# ---------------------------------------------------------------------------
# generation-aware result cache
# ---------------------------------------------------------------------------
def test_result_cache_generation_invalidation_unit():
    c = ResultCache(capacity=2)
    key = (b"q", (1,), "float32", 0.5)
    c.put(key, 3, np.arange(4.0), np.arange(4))
    hit = c.get(key, 3)
    assert hit is not None and c.hits == 1
    assert c.get(key, 4) is None  # newer generation: stale, dropped
    assert c.invalidations == 1 and len(c) == 0
    # LRU eviction at capacity
    for i in range(3):
        c.put((b"k", (1,), "f", float(i)), 0, np.zeros(1), np.zeros(1))
    assert len(c) == 2 and c.evictions == 1


def test_cache_hit_is_array_identical_and_invalidated_by_mutation(live_setup):
    r, qs = live_setup
    srv = BatchingServer(r, batch_size=4, max_wait_ms=1.0, cache_size=64)
    try:
        q = np.asarray(qs[0])
        cold = srv.search(q, timeout=60)
        assert not cold.cached
        hit = srv.search(q, timeout=60)
        assert hit.cached
        np.testing.assert_array_equal(hit.pids, cold.pids)
        np.testing.assert_array_equal(hit.scores, cold.scores)
        # a smaller per-request k is served from the same full-k entry
        small = srv.search(q, k=2, timeout=60)
        assert small.cached
        np.testing.assert_array_equal(small.pids, cold.pids[:2])

        gen_before = r.generation
        new_docs, _ = syn.embedding_corpus(5, dim=DIM, seed=99)
        srv.add_passages(new_docs)
        assert r.generation > gen_before
        fresh = srv.search(q, timeout=60)
        assert not fresh.cached  # generation bump made the entry stale
        cs = srv.stats()["cache"]
        assert cs["invalidations"] >= 1 and cs["hits"] >= 2
        # and the refreshed entry caches at the new generation
        assert srv.search(q, timeout=60).cached
    finally:
        srv.shutdown()


def test_cache_skips_insert_when_mutation_races_dispatch():
    class MutatingStub(StubRetriever):
        generation = 0

        def search_batch(self, qs, t_cs=None):
            out = super().search_batch(qs, t_cs=t_cs)
            self.generation += 1  # a mutation lands mid-dispatch
            return out

    srv = BatchingServer(
        MutatingStub(), batch_size=1, max_wait_ms=0.0, cache_size=32
    )
    try:
        q = _stub_query(1.0)
        srv.search(q, timeout=10)
        assert not srv.search(q, timeout=10).cached  # never inserted
        assert srv.cache.stats()["insertions"] == 0
    finally:
        srv.shutdown()


# ---------------------------------------------------------------------------
# replicas
# ---------------------------------------------------------------------------
def test_replica_pool_routes_to_least_outstanding():
    stubs = [StubRetriever(gated=True), StubRetriever(gated=True)]
    pool = ReplicaPool(
        stubs, batch_size=1, max_wait_ms=0.0, cache_size=None
    )
    try:
        f0 = pool.submit(_stub_query(0.0))
        busy = [s for s in pool.servers if s.outstanding][0]
        _wait(
            lambda: any(r.entered.is_set() for r in stubs),
            msg="first dispatch",
        )
        f = pool.submit(_stub_query(1.0))  # must land on the idle replica
        idle = [s for s in pool.servers if s is not busy][0]
        _wait(lambda: idle.retriever.entered.is_set(), msg="second dispatch")
        for s in stubs:
            s.gate.set()
        assert f.get(timeout=10).pids[0] == 1
        # a replica reports stats only once its request completed
        assert f0.get(timeout=10).pids[0] == 0
        st = pool.stats()
        assert st["n_replicas"] == 2 and st["submitted"] == 2
        assert [p["completed"] for p in st["replicas"]] == [1, 1]
        pool.assert_zero_retrace()
    finally:
        pool.shutdown()


def test_replica_pool_mutates_shared_index_once(live_setup):
    from repro.live.backend import LiveRetriever

    r, qs = live_setup
    # two replicas over ONE LiveIndex: the shared-mesh deployment
    replicas = [
        LiveRetriever(r.index, r.params),
        LiveRetriever(r.index, r.params),
    ]
    pool = ReplicaPool(replicas, batch_size=4, max_wait_ms=1.0)
    try:
        gen0 = r.index.generation
        new_docs, _ = syn.embedding_corpus(4, dim=DIM, seed=7)
        pids = pool.add_passages(new_docs)
        assert r.index.generation == gen0 + 1  # exactly one mutation
        assert pool.delete_passages(pids[:2]) == 2
        assert r.index.generation == gen0 + 2
        # both replicas serve the mutated corpus
        for s in pool.servers:
            res = s.search(np.asarray(qs[0]), timeout=60)
            assert res.pids.shape == (r.params.k,)
    finally:
        pool.shutdown()


# ---------------------------------------------------------------------------
# satellite: concurrent serving + mutation stress
# ---------------------------------------------------------------------------
def test_serving_stress_with_concurrent_mutations(live_setup):
    r, qs = live_setup
    srv = BatchingServer(r, batch_size=8, max_wait_ms=1.0, cache_size=256)
    n_threads, n_iters = 4, 12
    pool = [np.asarray(q) for q in qs[:4]]
    t_grid = (0.3, 0.4, 0.5)
    failures: list = []
    stop = threading.Event()

    def client(tid):
        rng = np.random.default_rng(tid)
        for i in range(n_iters):
            q = pool[rng.integers(len(pool))]
            t = t_grid[rng.integers(len(t_grid))]
            try:
                res = srv.search(q, t_cs=t, timeout=120)
                if res.pids.shape != (r.params.k,):
                    failures.append(("shape", res.pids.shape))
            except (QueueFull, DeadlineExceeded):
                pass  # typed shedding is an acceptable outcome
            except Exception as exc:  # hangs/untyped errors are not
                failures.append(("client", repr(exc)))

    def mutator():
        rng = np.random.default_rng(1234)
        added: list = []
        while not stop.is_set():
            op = rng.integers(3)
            try:
                if op == 0:
                    docs, _ = syn.embedding_corpus(
                        3, dim=DIM, seed=int(rng.integers(1 << 30))
                    )
                    added.extend(srv.add_passages(docs).tolist())
                elif op == 1 and added:
                    srv.delete_passages([added.pop()])
                else:
                    pid_map = srv.compact()  # remaps the whole pid space
                    added = [
                        int(pid_map[p]) for p in added if pid_map[p] >= 0
                    ]
            except Exception as exc:
                failures.append(("mutator", repr(exc)))
            time.sleep(0.05)

    threads = [
        threading.Thread(target=client, args=(i,)) for i in range(n_threads)
    ]
    mt = threading.Thread(target=mutator)
    mt.start()
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=300)
        assert not t.is_alive(), "client thread hung"
    stop.set()
    mt.join(timeout=60)
    assert not mt.is_alive(), "mutator thread hung"
    assert failures == []
    # quiescent now: every cached entry must match a direct search at the
    # final generation (no stale hit can survive the generation stamps)
    for q in pool:
        for t in t_grid:
            served = srv.search(q, t_cs=t, timeout=120)
            direct = r.search(q, t_cs=t)
            np.testing.assert_array_equal(served.pids, direct.pids)
            np.testing.assert_allclose(
                served.scores, direct.scores, rtol=1e-5
            )
    st = srv.stats()
    assert st["completed"] >= n_threads * n_iters
    # deterministic epilogue: a quiescent entry goes stale across one more
    # mutation and is invalidated (not served) on the next touch
    assert srv.search(pool[0], t_cs=t_grid[0], timeout=120).cached
    inval0 = srv.cache.stats()["invalidations"]
    docs, _ = syn.embedding_corpus(2, dim=DIM, seed=4242)
    srv.add_passages(docs)
    assert not srv.search(pool[0], t_cs=t_grid[0], timeout=120).cached
    assert srv.cache.stats()["invalidations"] == inval0 + 1
    srv.shutdown()
    with pytest.raises(ServerClosed):
        srv.submit(pool[0])


# ---------------------------------------------------------------------------
# future contract
# ---------------------------------------------------------------------------
def test_result_future_timeout_raises_queue_empty():
    f = ResultFuture()
    with pytest.raises(queue_mod.Empty):
        f.get(timeout=0.01)
    f.set("done")
    assert f.done() and f.get(timeout=0.01) == "done"


# ---------------------------------------------------------------------------
# observability: stats schema, gauges, spans
# ---------------------------------------------------------------------------
def test_stats_snapshot_schema_and_gauges(live_setup):
    """The stats() contract the dashboards scrape: every legacy key plus
    the queue-depth/outstanding gauges and the cache hit rate."""
    from repro.obs.metrics import MetricsRegistry
    from repro.obs.trace import Tracer

    r, qs = live_setup
    tracer, registry = Tracer(), MetricsRegistry()
    srv = BatchingServer(
        r, batch_size=4, max_wait_ms=1.0, tracer=tracer, registry=registry
    )
    try:
        assert srv.stats() == {}  # legacy contract: empty until completion
        srv.search(qs[0], timeout=60)
        srv.search(qs[0], timeout=60)  # cache hit
        st = srv.stats()
        expected = {
            # latency window
            "n", "window", "mean_ms", "p50_ms", "p99_ms",
            # counters
            "submitted", "completed", "cache_hits", "expired", "errors",
            "dispatches", "retraces",
            # admission + dispatch shape
            "shed", "rejected", "pending", "buckets",
            # observability additions
            "queue_depth", "outstanding", "cache",
        }
        assert expected <= set(st), expected - set(st)
        # a result future resolves inside _dispatch, a beat before the
        # dispatcher loop clears _inflight — poll the tiny race out
        deadline = time.perf_counter() + 5.0
        while srv.outstanding and time.perf_counter() < deadline:
            time.sleep(0.01)
        st = srv.stats()
        assert st["queue_depth"] == 0 and st["outstanding"] == 0
        cache = st["cache"]
        assert {"hits", "misses", "hit_rate", "size", "capacity"} <= set(cache)
        assert cache["hits"] == 1
        assert cache["hit_rate"] == pytest.approx(1 / 2)
        # the injected registry carries the same numbers as gauges
        snap = registry.snapshot()
        assert snap["serving_queue_depth"]["value"] == 0.0
        assert snap["serving_outstanding"]["value"] == 0.0
        # every dispatch-path span fired at least once
        names = {s.name for s in tracer.spans()}
        assert {
            "serve.queue_wait", "serve.pad", "serve.dispatch",
            "serve.truncate", "serve.cache_lookup",
        } <= names, names
        # queue_wait is recorded retroactively from submit time: its start
        # precedes the dispatch span's
        qw = tracer.spans("serve.queue_wait")[0]
        disp = tracer.spans("serve.dispatch")[0]
        assert qw.ts <= disp.ts
    finally:
        srv.shutdown()


def test_replica_pool_stats_aggregates_observability(live_setup):
    r, qs = live_setup
    pool = ReplicaPool([r], batch_size=4, max_wait_ms=1.0)
    try:
        pool.search(qs[0], timeout=60)
        pool.search(qs[0], timeout=60)
        st = pool.stats()
        for key in ("cache_hits", "cache_hit_rate", "queue_depth",
                    "expired", "shed"):
            assert key in st, key
        assert st["cache_hits"] == 1
        assert 0.0 < st["cache_hit_rate"] <= 1.0
    finally:
        pool.shutdown()
