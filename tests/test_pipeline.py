"""Batch-first stage pipeline: oracle equivalence, compile discipline,
shared sentinels/caps, batched kernels, the stage-2 code-window gather, and
the stage-1 single-matmul and stage-2 row-gather HLO regression guards."""
import dataclasses
import functools
import re
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import constants, retrieval
from repro.core import index as index_mod
from repro.core import pipeline, plaid, scoring
from repro.data import synthetic as syn
from repro.kernels import decompress as kdec
from repro.kernels import dispatch as kdisp
from repro.kernels import maxsim as kms
from repro.launch import hlo_analysis


@pytest.fixture(scope="module")
def small_index():
    docs, _ = syn.embedding_corpus(300, dim=32, min_len=6, max_len=20, seed=0)
    idx = index_mod.build_index(docs, num_centroids=256, nbits=2, kmeans_iters=4)
    qs, gold = syn.queries_from_docs(docs, 24, q_len=6)
    return idx, jnp.asarray(qs), gold


def vmap_search_oracle(eng, qs, q_masks=None):
    """The pre-refactor batch path — a plain ``jax.vmap`` over the
    single-query ``plaid._search`` monolith, with the engine's clamped
    static caps.  Defined here (its only remaining consumer) now that
    ``PlaidEngine.search_batch_oracle`` has completed its removal cycle."""
    if q_masks is None:
        q_masks = jnp.ones(qs.shape[:2], jnp.float32)
    fn = functools.partial(
        plaid._search, t_cs=eng.params.t_cs, **eng._kwargs()
    )
    return jax.vmap(fn, in_axes=(None, 0, 0))(eng.index, qs, q_masks)


# --------------------------------------------------------------------------
# Acceptance: batched pipeline == vmap-of-_search oracle
# --------------------------------------------------------------------------
@pytest.mark.parametrize("impl", ["ref", "pallas"])
def test_pipeline_matches_vmap_oracle(small_index, impl):
    """run_pipeline is rank-identical to the pre-refactor vmap path: same
    pids in every lane, scores within 1e-5, on both kernel impls."""
    idx, qs, _ = small_index
    eng = plaid.PlaidEngine(idx, plaid.params_for_k(10, impl=impl))
    new_s, new_p = eng.search_batch(qs)
    old_s, old_p = vmap_search_oracle(eng, qs)
    np.testing.assert_array_equal(np.asarray(new_p), np.asarray(old_p))
    np.testing.assert_allclose(
        np.asarray(new_s), np.asarray(old_s), atol=1e-5
    )


def test_single_query_is_a_squeeze_of_the_batch(small_index):
    """B=1 is not a separate code path: search(q) == search_batch(q[None])."""
    idx, qs, _ = small_index
    eng = plaid.PlaidEngine(idx, plaid.params_for_k(10))
    s1, p1 = eng.search(qs[0])
    sb, pb = eng.search_batch(qs[:1])
    np.testing.assert_array_equal(np.asarray(p1), np.asarray(pb[0]))
    np.testing.assert_array_equal(np.asarray(s1), np.asarray(sb[0]))


def test_pipeline_t_cs_sweep_one_compile_per_bucket(small_index):
    """Acceptance: a t_cs sweep at B>1 retraces zero times — one compile
    per static-shape bucket, with the threshold a traced operand."""
    idx, qs, _ = small_index
    eng = plaid.PlaidEngine(idx, plaid.params_for_k(10))
    eng.search_batch(qs, t_cs=0.5)  # warm the (B, nq) bucket
    n0 = plaid.trace_count()
    for t_cs in (0.45, 0.3, -1e9, 0.7):
        eng.search_batch(qs, t_cs=t_cs)
    assert plaid.trace_count() == n0, "t_cs sweep must not retrace"
    # params.t_cs is normalized out of the cache key too
    eng2 = plaid.PlaidEngine(
        idx, dataclasses.replace(plaid.params_for_k(10), t_cs=0.31)
    )
    eng2.search_batch(qs)
    assert plaid.trace_count() == n0


# --------------------------------------------------------------------------
# Stage functions against their single-query references
# --------------------------------------------------------------------------
def test_stage1_scores_match_per_lane_reference(small_index):
    idx, qs, _ = small_index
    got = pipeline.stage1_scores_batched(idx, qs)
    want = jnp.stack([scoring.centroid_scores(q, idx.centroids) for q in qs])
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)


def test_candidate_generation_batched_matches_per_lane(small_index):
    idx, qs, _ = small_index
    s_cq = pipeline.stage1_scores_batched(idx, qs)
    got = pipeline.candidate_generation_batched(idx, s_cq, 2, 128)
    for b in range(qs.shape[0]):
        want = plaid.candidate_generation(idx, s_cq[b], 2, 128)
        np.testing.assert_array_equal(np.asarray(got[b]), np.asarray(want))


def test_shared_gather_matches_per_lane_gather(small_index):
    """The deduplicated pool gather reproduces per-lane gather_doc_tokens
    bitwise (codes, -1 fill, and validity masks)."""
    idx, qs, _ = small_index
    s_cq = pipeline.stage1_scores_batched(idx, qs)
    cands = pipeline.candidate_generation_batched(idx, s_cq, 2, 64)
    codes_b, valid_b = pipeline.gather_candidate_tokens_shared(idx, cands)
    for b in range(qs.shape[0]):
        codes_1, valid_1 = scoring.gather_doc_tokens(
            idx.codes, idx.doc_offsets, idx.doc_lens, cands[b],
            idx.doc_maxlen, fill=-1,
        )
        np.testing.assert_array_equal(np.asarray(codes_b[b]), np.asarray(codes_1))
        np.testing.assert_array_equal(np.asarray(valid_b[b]), np.asarray(valid_1))


def _code_windows(case: str, doc_maxlen: int, rng):
    """(starts, lens, pids) of code windows over a payload of ``nt`` slots
    that probe the edges of the aligned W-slot rows the gather fetches."""
    W = 128 * -(-doc_maxlen // 128)
    nt = 3 * W + 37
    edges = [0, W - 1, W, 2 * W - 1]
    if case == "last_token":  # windows that end on the payload's last slot
        lens = [doc_maxlen, 1, 70]
        starts = [nt - n for n in lens]
    elif case == "row_edges":  # each edge start, at both extreme lengths
        starts = edges * 2
        lens = [doc_maxlen] * 4 + [1] * 4
    elif case == "lengths":
        lens = list(rng.integers(1, doc_maxlen + 1, 64))
        starts = [int(rng.integers(0, nt - n + 1)) for n in lens]
    elif case == "pad":
        starts, lens = edges, [doc_maxlen, 1, 5, doc_maxlen]
        return nt, starts, lens, np.array([-1, 0, -1, 3, 2, -1], np.int32)
    return nt, starts, lens, np.arange(len(starts), dtype=np.int32)


def _windows_oracle(values, starts, lens, pids, doc_maxlen):
    """Plain per-token indexing: ``values[start + pos]`` for pos < len."""
    out = np.full((len(pids), doc_maxlen), -1, values.dtype)
    for i, p in enumerate(pids):
        if p >= 0:
            for pos in range(lens[p]):
                out[i, pos] = values[starts[p] + pos]
    return out


@pytest.mark.parametrize("doc_maxlen", [128, 180, 300])
@pytest.mark.parametrize(
    "case", ["last_token", "row_edges", "lengths", "pad", "shared_pool"]
)
def test_code_window_gather_matches_per_token_indexing(case, doc_maxlen):
    """The 1-D path of ``gather_doc_tokens`` (two aligned rows and a shift
    per window) returns what per-token indexing returns, bit for bit, with
    -1 past each passage's length and in every slot of a ``-1`` pad;
    ``shared_pool`` runs it under the pipeline's B = 2 pool, whose lanes
    share candidates, over a CSR whose last passage ends on the last
    token."""
    rng = np.random.default_rng(doc_maxlen)
    if case == "shared_pool":
        lens = rng.integers(1, doc_maxlen + 1, 40).astype(np.int32)
        lens[[3, 17, 39]] = doc_maxlen
        starts = np.concatenate([[0], np.cumsum(lens)]).astype(np.int32)
        nt = int(starts[-1])
        values = rng.integers(0, 1 << 30, nt).astype(np.int32)
        cands = np.full((2, 24), -1, np.int32)
        cands[0, :16] = np.arange(0, 32, 2)  # lanes share pids 12..30
        cands[1, :20] = np.sort(rng.choice(np.arange(12, 40), 20, False))
        index = types.SimpleNamespace(
            codes=jnp.asarray(values), doc_offsets=jnp.asarray(starts),
            doc_lens=jnp.asarray(lens), doc_maxlen=doc_maxlen,
        )
        codes, valid = pipeline.gather_candidate_tokens_shared(
            index, jnp.asarray(cands)
        )
        for b in range(2):
            want = _windows_oracle(values, starts, lens, cands[b], doc_maxlen)
            np.testing.assert_array_equal(np.asarray(codes[b]), want)
            np.testing.assert_array_equal(np.asarray(valid[b]), want != -1)
        return
    nt, starts, lens, pids = _code_windows(case, doc_maxlen, rng)
    values = rng.integers(0, 1 << 30, nt).astype(np.int32)
    got, valid = scoring.gather_doc_tokens(
        jnp.asarray(values),
        jnp.asarray(np.append(starts, nt).astype(np.int32)),
        jnp.asarray(np.asarray(lens, np.int32)),
        jnp.asarray(pids),
        doc_maxlen,
        fill=-1,
    )
    want = _windows_oracle(values, starts, lens, pids, doc_maxlen)
    np.testing.assert_array_equal(np.asarray(got), want)
    np.testing.assert_array_equal(np.asarray(valid), want != -1)


def test_diag_batched_matches_single_query(small_index):
    """Satellite: diag=True under search_batch — (B,) counters that agree
    with the single-query diagnostics lane by lane."""
    idx, qs, _ = small_index
    eng = plaid.PlaidEngine(idx, plaid.params_for_k(10))
    B = qs.shape[0]
    _, _, diag_b = eng.search_batch(qs, diag=True)
    assert set(diag_b) == {
        "stage1_candidates", "stage2_kept_centroids", "stage3_survivors",
    }
    for name, v in diag_b.items():
        assert v.shape == (B,), name
    for b in (0, B // 2, B - 1):
        _, _, diag_1 = eng.search(qs[b], diag=True)
        for name in diag_b:
            assert int(diag_b[name][b]) == int(diag_1[name]), (name, b)


def test_facade_search_batch_diagnostics(small_index):
    """The vmap'd-then, batched-now diagnostics path through the facade."""
    idx, qs, _ = small_index
    r = retrieval.from_index(
        idx, backend="plaid",
        params=retrieval.SearchParams(k=5, nprobe=2, ndocs=64,
                                      candidate_cap=128),
    )
    res = r.search_batch(qs, with_diagnostics=True)
    B = qs.shape[0]
    assert res.diagnostics["stage1_candidates"].shape == (B,)
    assert res.diagnostics["stage3_survivors"].shape == (B,)
    assert (res.diagnostics["stage2_kept_centroids"] >= 0).all()


# --------------------------------------------------------------------------
# Satellites: shared sentinel + candidate_cap single source of truth
# --------------------------------------------------------------------------
def test_neg_sentinel_single_source():
    """Kernel and reference sentinels agree — and are the same constant.

    ``kernels.ref`` is pinned too, and ``kernels.fused_score`` scores through
    ``kernels.decompress``: a locally redefined sentinel would silently
    reorder equal-score ties between the fused / unfused / ref paths without
    failing any rank test."""
    from repro.kernels import fused_score as kfs
    from repro.kernels import ref as kref

    assert scoring.NEG == constants.NEG
    assert kms.NEG == constants.NEG
    assert kdec.NEG == constants.NEG
    assert plaid.NEG == constants.NEG
    assert pipeline.NEG == constants.NEG
    assert kref.NEG is constants.NEG
    assert kfs.decompress_and_score_batched_pallas.__module__ == kdec.__name__


def test_candidate_cap_single_source_of_truth():
    cap = constants.DEFAULT_CANDIDATE_CAP
    assert plaid.SearchParams().candidate_cap == cap
    assert retrieval.SearchParams().candidate_cap == cap
    assert plaid.params_for_k(10).candidate_cap == cap
    assert retrieval.params_for_k(10).candidate_cap == cap
    # explicit overrides still win
    assert plaid.params_for_k(10, candidate_cap=512).candidate_cap == 512
    assert retrieval.params_for_k(10, candidate_cap=512).candidate_cap == 512


def test_platform_aware_interpret_dispatch():
    """interpret=None resolves via jax.default_backend(); explicit wins."""
    expect = jax.default_backend() != "tpu"
    assert kdisp.default_interpret() == expect
    assert kdisp.resolve_interpret(None) == expect
    assert kdisp.resolve_interpret(True) is True
    assert kdisp.resolve_interpret(False) is False


# --------------------------------------------------------------------------
# Batched Pallas kernels vs per-lane oracles
# --------------------------------------------------------------------------
def test_batched_centroid_interaction_kernel_matches_ref():
    rng = np.random.default_rng(0)
    B, K, nq, nd, L = 3, 48, 5, 37, 9
    s_cq = jnp.asarray(rng.normal(size=(B, K, nq)).astype(np.float32))
    codes = rng.integers(-1, K, size=(B, nd, L)).astype(np.int32)
    keep = jnp.asarray(rng.random((B, K)) > 0.3)
    q_mask = jnp.asarray((rng.random((B, nq)) > 0.2).astype(np.float32))
    got = kms.centroid_interaction_batched_pallas(
        s_cq, jnp.asarray(codes), keep, q_mask, doc_block=8, interpret=True
    )
    want = pipeline.centroid_interaction_batched(
        s_cq, jnp.asarray(codes), q_mask, keep
    )
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)


def test_batched_decompress_score_kernel_matches_ref(small_index):
    idx, qs, _ = small_index
    s_cq = pipeline.stage1_scores_batched(idx, qs[:4])
    cands = pipeline.candidate_generation_batched(idx, s_cq, 2, 32)
    codes_b, valid_b = pipeline.gather_candidate_tokens_shared(idx, cands)
    B, nd = cands.shape
    res_blk, _ = scoring.gather_doc_tokens(
        idx.residuals, idx.doc_offsets, idx.doc_lens,
        cands.reshape(-1), idx.doc_maxlen, fill=jnp.uint8(0),
    )
    res_blk = res_blk.reshape(B, nd, idx.doc_maxlen, -1)
    q_masks = jnp.ones(qs[:4].shape[:2], jnp.float32)
    got = kdec.decompress_and_score_batched_pallas(
        qs[:4], q_masks, codes_b, res_blk, valid_b,
        idx.centroids, idx.weights, nbits=idx.nbits, doc_block=4,
        interpret=True,
    )
    want = pipeline.decompress_score_batched(
        idx, qs[:4], q_masks, codes_b, res_blk, valid_b
    )
    got = np.where(np.asarray(cands) >= 0, np.asarray(got), 0)
    want = np.where(np.asarray(cands) >= 0, np.asarray(want), 0)
    np.testing.assert_allclose(got, want, atol=1e-4, rtol=1e-5)


# --------------------------------------------------------------------------
# Acceptance: the HLO contains exactly ONE stage-1 C·Qᵀ dot per batch
# --------------------------------------------------------------------------
def test_stage1_lowers_to_single_batchwide_matmul():
    """Regression guard: the batched stage 1 must not re-materialize
    per-lane matmuls (python loops / scans over lanes would show up as B
    dots, or one dot under a trip-count-B while loop)."""
    docs, _ = syn.embedding_corpus(
        80, dim=16, min_len=9, max_len=14, seed=0
    )
    idx = index_mod.build_index(docs, num_centroids=32, nbits=2, kmeans_iters=2)
    K, nq, B = idx.num_centroids, 5, 3
    qs = jnp.asarray(
        np.random.default_rng(0).normal(size=(B, nq, 16)).astype(np.float32)
    )
    params = plaid.SearchParams(k=4, nprobe=2, ndocs=16, candidate_cap=32)
    lowered = pipeline.run_pipeline_jit.lower(
        idx, qs, jnp.ones((B, nq), jnp.float32), jnp.float32(0.4),
        params=params,
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
            # a per-lane (K, nq) stage-1 dot would betray lane-by-lane
            # re-materialization
            assert not (n == K * nq and K in dims), ins.raw
    assert len(stage1) == 1, [s[1].raw for s in stage1]
    assert stage1[0][2] == 1.0, "stage-1 dot must not sit inside a loop"


# --------------------------------------------------------------------------
# Acceptance: stage 2 gathers the codes by W-slot rows, not by token
# --------------------------------------------------------------------------
def test_stage2_code_gather_lowers_to_row_gather():
    """Regression guard: the stage-2 CSR gather of ``index.codes`` fetches
    each candidate's window as two rows of W = 128 * ceil(L / 128) slots
    (a gather with slice size (1, W)); no gather under ``plaid.s2.gather``
    fetches the codes a slot at a time (slice size 1, one index per slot
    of the (pool, L) block)."""
    docs, _ = syn.embedding_corpus(
        80, dim=16, min_len=9, max_len=14, seed=0
    )
    idx = index_mod.build_index(docs, num_centroids=32, nbits=2, kmeans_iters=2)
    nq, B, cap = 5, 3, 32
    L, W = idx.doc_maxlen, 128 * -(-idx.doc_maxlen // 128)
    qs = jnp.asarray(
        np.random.default_rng(0).normal(size=(B, nq, 16)).astype(np.float32)
    )
    params = plaid.SearchParams(k=4, nprobe=2, ndocs=16, candidate_cap=cap)
    hlo = pipeline.run_pipeline_jit.lower(
        idx, qs, jnp.ones((B, nq), jnp.float32), jnp.float32(0.4),
        params=params,
    ).compile().as_text()
    gathers = [
        ins
        for comp in hlo_analysis.parse_module(hlo).values()
        for ins in comp.instrs
        if ins.op == "gather" and "/plaid.s2.gather/" in ins.raw
    ]
    sizes = [
        (hlo_analysis._shape_dims(g.rtype),
         re.search(r"slice_sizes=\{([\d,]*)\}", g.raw).group(1))
        for g in gathers
    ]
    rows = [d for d, s in sizes if s == f"1,{W}"]
    assert [int(np.prod(d)) for d in rows] == [B * cap * 2 * W], sizes
    per_slot = [d for d, s in sizes if s == "1" and np.prod(d) >= B * cap * L]
    assert not per_slot, sizes
