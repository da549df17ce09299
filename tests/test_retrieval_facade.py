"""The retrieval facade: registry round-trips, dynamic-t_cs compile
discipline, SearchResult metadata, server validation, deprecation shims."""
import tempfile
import warnings

import jax.numpy as jnp
import numpy as np
import pytest

from repro import retrieval
from repro.core import engine_sharded, index as index_mod, plaid, vanilla
from repro.data import synthetic as syn

BACKENDS = ["vanilla", "plaid", "plaid-pallas", "plaid-sharded"]
ALL_BACKENDS = BACKENDS + ["live", "live-pallas"]  # live covered in test_live

PARAMS = retrieval.SearchParams(
    k=5, nprobe=2, t_cs=0.4, ndocs=64, candidate_cap=128
)


@pytest.fixture(scope="module")
def built():
    docs, _ = syn.embedding_corpus(200, dim=32, seed=0)
    idx = index_mod.build_index(docs, num_centroids=64, nbits=2, kmeans_iters=3)
    qs, gold = syn.queries_from_docs(docs, 8)
    return docs, idx, jnp.asarray(qs), gold


def _retriever(idx, backend):
    return retrieval.from_index(idx, backend=backend, params=PARAMS)


# --------------------------------------------------------------------------
# registry + construction
# --------------------------------------------------------------------------
def test_registry_lists_builtin_backends():
    assert set(ALL_BACKENDS) <= set(retrieval.list_backends())


def test_unknown_backend_raises_with_choices():
    with pytest.raises(KeyError, match="plaid"):
        retrieval.get_backend("no-such-engine")


def test_build_from_corpus_embeddings():
    # 16 queries / 4 Lloyd iterations: enough statistics that the recall
    # floor tests clustering QUALITY, not which local optimum a particular
    # PRNG stream lands on (2 iterations over 4 queries flipped with the
    # kmeans key-split fix)
    docs, _ = syn.embedding_corpus(80, dim=16, seed=1)
    r = retrieval.build(
        docs,
        retrieval.RetrieverConfig(
            backend="plaid",
            params=PARAMS,
            index=dict(num_centroids=64, kmeans_iters=4),
        ),
    )
    qs, gold = syn.queries_from_docs(docs, 16)
    res = r.search_batch(jnp.asarray(qs))
    assert (np.asarray(res.pids[:, 0]) == gold).mean() >= 0.75


@pytest.mark.parametrize("backend", BACKENDS)
def test_facade_matches_prerefactor_engine(built, backend):
    """Acceptance: every backend returns the pre-refactor engine's top-k."""
    docs, idx, qs, gold = built
    res = _retriever(idx, backend).search_batch(qs)
    if backend == "vanilla":
        oracle = vanilla.VanillaEngine(
            idx,
            vanilla.VanillaParams(
                k=5, nprobe=2, ncandidates=128, ndocs_cap=64
            ),
        )
        _, want = oracle.search_batch(qs)
    elif backend in ("plaid", "plaid-pallas"):
        oracle = plaid.PlaidEngine(
            idx,
            plaid.SearchParams(
                k=5, nprobe=2, t_cs=0.4, ndocs=64, candidate_cap=128,
                impl="pallas" if backend == "plaid-pallas" else "ref",
            ),
        )
        _, want = oracle.search_batch(qs)
    else:  # plaid-sharded, single local device -> one shard
        from repro.launch.mesh import make_local_mesh

        sp = plaid.SearchParams(
            k=5, nprobe=2, t_cs=0.4, ndocs=64, candidate_cap=128
        )
        search = engine_sharded.make_sharded_search(
            make_local_mesh(), sp, docs_per_shard=idx.num_passages,
            static_meta=engine_sharded.static_meta_of(idx),
        )
        _, want = search(idx, qs, jnp.ones(qs.shape[:2], jnp.float32))
    np.testing.assert_array_equal(np.asarray(res.pids), np.asarray(want))


@pytest.mark.parametrize("backend", BACKENDS)
def test_save_load_roundtrip_identical_topk(built, backend):
    docs, idx, qs, gold = built
    r = _retriever(idx, backend)
    want = np.asarray(r.search_batch(qs).pids)
    with tempfile.TemporaryDirectory() as d:
        r.save(d)
        r2 = retrieval.load(d)  # backend + params read from retriever.json
        assert r2.backend_name == backend
        assert r2.params == PARAMS
        got = np.asarray(r2.search_batch(qs).pids)
    np.testing.assert_array_equal(want, got)


def test_load_sniffs_bare_index_dir(built):
    """Directories written by the raw indexer (no retriever.json) load."""
    docs, idx, qs, gold = built
    from repro.core import indexer

    with tempfile.TemporaryDirectory() as d:
        indexer.save_index(d, idx)
        r = retrieval.load(d, params=PARAMS)
    assert r.backend_name == "plaid"
    assert r.search_batch(qs).pids.shape == (qs.shape[0], 5)


# --------------------------------------------------------------------------
# static/dynamic parameter split
# --------------------------------------------------------------------------
def test_params_split_fields():
    p = retrieval.SearchParams()
    assert set(retrieval.STATIC_FIELDS) == set(p.static_dict())
    assert set(retrieval.DYNAMIC_FIELDS) == set(p.dynamic_dict())
    assert "t_cs" in retrieval.DYNAMIC_FIELDS
    assert "candidate_cap" in retrieval.STATIC_FIELDS
    # one documented score_dtype default, everywhere (satellite: the old
    # _search default was bfloat16 while SearchParams said float32)
    assert p.score_dtype == retrieval.DEFAULT_SCORE_DTYPE == "float32"
    import inspect

    assert (
        inspect.signature(plaid._search.__wrapped__)
        .parameters["score_dtype"].default
        == "float32"
    )


def test_dynamic_t_cs_zero_recompiles(built):
    """Sweeping t_cs at search time reuses the compiled program."""
    docs, idx, qs, gold = built
    r = _retriever(idx, "plaid")
    # warm both variants (plain + diagnostics) at the compiled static shape
    r.search(qs[0], t_cs=0.4)
    r.search(qs[0], t_cs=0.4, with_diagnostics=True)
    r.search_batch(qs, t_cs=0.4)
    n0 = plaid.trace_count()
    survivors = []
    for t_cs in (0.5, 0.45, 0.3, -1e9):
        res = r.search(qs[0], t_cs=t_cs, with_diagnostics=True)
        survivors.append(res.diagnostics["stage2_kept_centroids"])
        r.search_batch(qs, t_cs=t_cs)
    assert plaid.trace_count() == n0, "t_cs sweep must not retrace/recompile"
    # the sweep actually changed pruning: -1e9 keeps every centroid
    assert survivors[-1] == idx.num_centroids
    assert min(survivors[:-1]) < survivors[-1]


def test_static_cap_change_does_recompile(built):
    """Contrast: changing a static cap is a new program (documented cost)."""
    docs, idx, qs, gold = built
    _retriever(idx, "plaid").search(qs[0])
    n0 = plaid.trace_count()
    r2 = retrieval.from_index(
        idx, backend="plaid", params=PARAMS.replace(ndocs=32)
    )
    r2.search(qs[0])
    assert plaid.trace_count() > n0


def test_describe_reports_split_and_compile_stats(built):
    docs, idx, qs, gold = built
    r = _retriever(idx, "plaid")
    d = r.describe()
    assert d["backend"] == "plaid"
    assert tuple(d["static_fields"]) == retrieval.STATIC_FIELDS
    assert tuple(d["dynamic_fields"]) == retrieval.DYNAMIC_FIELDS
    assert d["static"]["candidate_cap"] == 128
    assert d["dynamic"] == {"t_cs": 0.4}
    assert d["index"]["num_passages"] == idx.num_passages
    assert d["compile"]["trace_count"] >= 0
    # vanilla advertises no dynamic knobs
    assert _retriever(idx, "vanilla").describe()["dynamic_fields"] == ()


# --------------------------------------------------------------------------
# SearchResult metadata
# --------------------------------------------------------------------------
def test_search_result_metadata(built):
    docs, idx, qs, gold = built
    r = _retriever(idx, "plaid")
    res = r.search(qs[0], with_diagnostics=True)
    assert res.backend == "plaid" and res.k == 5
    assert res.latency_ms is not None and res.latency_ms > 0
    assert res.t_cs == pytest.approx(0.4)
    assert set(res.diagnostics) == {
        "stage1_candidates", "stage2_kept_centroids", "stage3_survivors",
    }
    assert 0 < res.diagnostics["stage3_survivors"] <= 128
    # tuple-compat iteration for migrating call sites
    scores, pids = res
    np.testing.assert_array_equal(np.asarray(pids), np.asarray(res.pids))
    # batched results carry per-query diagnostics
    resb = r.search_batch(qs, with_diagnostics=True)
    assert resb.diagnostics["stage2_kept_centroids"].shape == (qs.shape[0],)


def test_diagnostics_unsupported_backends_raise(built):
    docs, idx, qs, gold = built
    for backend in ("vanilla", "plaid-sharded"):
        r = _retriever(idx, backend)
        with pytest.raises(ValueError, match="with_diagnostics"):
            r.search(qs[0], with_diagnostics=True)
        with pytest.raises(ValueError, match="with_diagnostics"):
            r.search_batch(qs, with_diagnostics=True)


@pytest.mark.parametrize("backend", ["plaid", "plaid-pallas"])
def test_search_batch_spans_nest_under_the_caller(built, backend):
    """``search_batch`` records ``retrieval.search_batch`` into the process
    tracer as a child of the caller's open span (another tracer's), with
    ``retrieval.launch`` then ``retrieval.block`` inside it."""
    from repro.obs import get_tracer
    from repro.obs.trace import Tracer

    _, idx, qs, _ = built
    r = _retriever(idx, backend)
    r.search_batch(qs)  # compiled
    caller = Tracer()
    with caller.span("serve.dispatch"):
        r.search_batch(qs)
    (outer,) = caller.spans()
    spans = get_tracer().spans()
    (call,) = [s for s in spans if s.name == "retrieval.search_batch"
               and s.parent == outer.sid]
    assert call.attrs == {"backend": backend}
    (launch,) = [s for s in spans if s.parent == call.sid
                 and s.name == "retrieval.launch"]
    (block,) = [s for s in spans if s.parent == call.sid
                and s.name == "retrieval.block"]
    assert outer.ts <= call.ts <= launch.ts
    assert launch.ts + launch.dur <= block.ts
    assert block.ts + block.dur <= call.ts + call.dur <= outer.ts + outer.dur


def test_search_request_object(built):
    docs, idx, qs, gold = built
    r = _retriever(idx, "plaid")
    req = retrieval.SearchRequest(q=qs[0], t_cs=0.3, with_diagnostics=True)
    res = r.search(req)
    assert res.t_cs == pytest.approx(0.3) and res.diagnostics is not None


# --------------------------------------------------------------------------
# deprecation cycle completed: the shims must stay gone
# --------------------------------------------------------------------------
def test_deprecated_shims_removed():
    """PlaidSearcher/VanillaSearcher, search_batch_oracle and the server's
    ``searcher`` alias finished their announced removal timeline."""
    from repro.serving.server import BatchingServer

    assert not hasattr(plaid, "PlaidSearcher")
    assert not hasattr(vanilla, "VanillaSearcher")
    assert not hasattr(plaid.PlaidEngine, "search_batch_oracle")
    assert "searcher" not in vars(BatchingServer)


# --------------------------------------------------------------------------
# batching server over the facade
# --------------------------------------------------------------------------
def test_server_takes_facade_retriever_and_validates(built):
    from repro.serving.server import BatchingServer

    docs, idx, qs, gold = built
    r = _retriever(idx, "plaid")
    want = np.asarray(r.search_batch(qs).pids)
    srv = BatchingServer(r, batch_size=4, max_wait_ms=5.0)
    try:
        # malformed queries fail fast at submit, with clear messages
        with pytest.raises(ValueError, match="query matrix"):
            srv.submit(np.ones(16, np.float32))  # 1-D
        with pytest.raises(ValueError, match="floating"):
            srv.submit(np.ones((4, 32), np.int32))
        with pytest.raises(ValueError, match="dim"):
            srv.submit(np.ones((4, 8), np.float32))  # wrong dim
        futs = [srv.submit(np.asarray(qs[i])) for i in range(qs.shape[0])]
        got = [f.get(timeout=60) for f in futs]
        # nq fixed by the first request
        with pytest.raises(ValueError, match="shape"):
            srv.submit(np.ones((qs.shape[1] + 1, 32), np.float32))
    finally:
        srv.shutdown()
    for i, res in enumerate(got):
        np.testing.assert_array_equal(res.pids, want[i])
        assert res.latency_ms > 0
    st = srv.stats()
    assert st["n"] == qs.shape[0] and st["p99_ms"] >= st["p50_ms"]


def test_server_stats_thread_safe_under_load(built):
    """stats() concurrent with the dispatcher appending must not crash."""
    import threading

    from repro.serving.server import BatchingServer

    docs, idx, qs, gold = built
    srv = BatchingServer(_retriever(idx, "plaid"), batch_size=2, max_wait_ms=1.0)
    errors = []

    def poll():
        try:
            for _ in range(200):
                srv.stats()
        except Exception as e:  # pragma: no cover
            errors.append(e)

    try:
        poller = threading.Thread(target=poll)
        poller.start()
        futs = [srv.submit(np.asarray(qs[i % qs.shape[0]])) for i in range(12)]
        for f in futs:
            f.get(timeout=60)
        poller.join()
    finally:
        srv.shutdown()
    assert not errors
    assert srv.stats()["n"] == 12
