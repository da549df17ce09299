"""Batch-first PLAID stage pipeline: composable stages over a query batch.

The monolithic single-query ``plaid._search`` served batches by ``jax.vmap``
— every lane redundantly recomputed the stage-1 ``C·Qᵀ`` score matrix,
re-gathered overlapping candidate doc tokens, and launched per-lane kernels.
This module decomposes the 4-stage pipeline (paper Fig. 5) into explicitly
batched stage functions; ``run_pipeline`` is the one jit entry point for
B >= 1 (B = 1 is a squeeze at the caller, not a separate code path):

``stage1_scores_batched``
    ONE ``C·Qᵀ`` matmul for the whole (B, nq) query batch — the (K, d)
    centroid matrix streams from HBM once per batch, and the HLO contains
    exactly one stage-1 dot (regression-guarded via ``launch.hlo_analysis``).
``candidate_generation_batched``
    Per-lane top-``nprobe`` probe + IVF union, batched over B.
``gather_candidate_tokens_shared``
    ONE code gather for the whole batch: lanes' candidate sets are
    deduplicated into a shared sorted pool, each pooled passage's code
    window is fetched as two aligned rows of the packed codes, and the
    pool is re-expanded per lane — candidates common across the batch are
    fetched once.
``centroid_interaction_batched`` / ``decompress_score_batched``
    Stages 2–4 over (B, cap) candidate blocks; with ``impl="pallas"`` these
    dispatch to the batched-grid kernels (``repro.kernels.ops``).

Compile discipline matches ``_search``: shape caps (``k``, ``nprobe``,
``ndocs``, ``candidate_cap``) and codegen choices (``impl``,
``score_dtype``) are static; the pruning threshold ``t_cs`` is TRACED, so
sweeping it at serve time never recompiles.  ``params.t_cs`` is normalized
out of the jit cache key — only the per-call traced value matters.

The old vmap-of-``_search`` path is no longer an engine entry point: the
numerical oracle the pipeline is validated against is a plain
``jax.vmap(_search)`` defined locally in ``tests/test_pipeline.py``.

Each stage runs under a flat ``jax.named_scope`` (``STAGES``), which names
it in the compiled program's ``op_name`` metadata and changes nothing
else.  A device trace carries each operation's ``op_name``, so a reader
sums the operations' time by stage.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp

from repro.constants import NEG
from repro.core import residual_codec as rc
from repro.core import scoring
from repro.core.index import PlaidIndex
from repro.obs.funnel import FunnelStats

#: int32 key standing in for the -1 "padded slot" sentinel wherever a SORTED
#: order is needed (pool construction): real pids < num_passages, so the max
#: int32 can never collide and sorts after every real pid.
_PAD_KEY = jnp.iinfo(jnp.int32).max

#: The pipeline's stage scopes, in the order the stages run: stage 1's
#: ``C·Qᵀ`` and per-token probe top-k; the IVF walk, candidate union and
#: cap; stage 2's shared CSR gather of the candidates' codes; stage 2's
#: pruned interaction and top-``ndocs``; stage 3; stage 4 and the final
#: top-k.
STAGES = (
    "plaid.s1", "plaid.cand", "plaid.s2.gather", "plaid.s2.score",
    "plaid.s3", "plaid.s4",
)


_N_TRACES = 0


def trace_count() -> int:
    """Number of times the batched pipeline has been (re)traced/compiled."""
    return _N_TRACES


# --------------------------------------------------------------------------
# Stage 1 — batched query-centroid scores + candidate generation
# --------------------------------------------------------------------------
def stage1_scores_batched(
    index: PlaidIndex,
    qs: jax.Array,
    score_dtype: str = "float32",
    stage1_dtype: str = "float32",
) -> jax.Array:
    """(B, nq, d) queries -> (B, K, nq) score tensor via ONE ``C·Qᵀ`` dot.

    The batch is flattened into the matmul's N dimension — (K, d) x
    (d, B*nq) — so XLA emits a single dot and the centroid matrix is read
    once per batch, not once per lane (§Perf S1).

    ``stage1_dtype`` picks the matmul's OPERAND precision (the PLAID
    reproducibility study shows centroid-stage scores tolerate reduced
    precision): ``"float32"`` is the oracle; ``"bfloat16"`` casts both
    operands (halves centroid-table read traffic); ``"int8"`` streams the
    index's weight-only-quantized table ``centroids_q`` and rescales by the
    per-row dequant scale after the dot.  Accumulation is f32 in every
    mode, and stage 4 rescores exactly, so under lossless caps the final
    ranking is identical (``tests/test_fused.py``).
    """
    B, nq, d = qs.shape
    flat = qs.astype(jnp.float32).reshape(B * nq, d)
    if stage1_dtype == "float32":
        C = index.centroids.astype(jnp.float32)
        s = C @ flat.T  # (K, B*nq) — the one stage-1 dot
    elif stage1_dtype == "bfloat16":
        C = index.centroids.astype(jnp.bfloat16)
        s = jax.lax.dot(
            C, flat.astype(jnp.bfloat16).T,
            preferred_element_type=jnp.float32,
        )
    elif stage1_dtype == "int8":
        # Weight-only: C ~= centroids_q * scale[:, None], so C @ Qᵀ ~=
        # scale[:, None] * (centroids_q @ Qᵀ).  The int values (|q| <= 127)
        # are exact in f32, so the dot itself is deterministic.
        Cq = index.centroids_q.astype(jnp.float32)
        s = (Cq @ flat.T) * index.centroids_scale[:, None]
    else:
        raise ValueError(f"unknown stage1_dtype: {stage1_dtype!r}")
    s = s.reshape(s.shape[0], B, nq).transpose(1, 0, 2)  # (B, K, nq)
    return s.astype(jnp.dtype(score_dtype))


def candidate_generation_batched(
    index: PlaidIndex,
    s_cq: jax.Array,
    nprobe: int,
    candidate_cap: int,
    alive: jax.Array | None = None,
    *,
    with_stats: bool = False,
    nprobe_t: jax.Array | None = None,
):
    """(B, K, nq) scores -> (B, candidate_cap) sorted unique pids, -1 pad.

    Identical per-lane semantics to ``plaid.candidate_generation`` (same
    top-k tie-breaking, same IVF walk), batched over B.  ``alive`` is the
    live-index tombstone mask: dead pids are nulled BEFORE the
    ``candidate_cap`` truncation, so tombstoned passages never consume cap
    slots a rebuild's IVF would have given to live ones.

    ``with_stats=True`` (the funnel-telemetry path) additionally returns a
    per-lane ``(B,)`` count of the DISTINCT tombstoned passages the alive
    mask removed (clamped at ``candidate_cap`` distinct dead pids — the
    same static bound the live candidates get).

    ``nprobe_t`` is an optional TRACED effective probe count
    ``<= nprobe`` (``exec.bucketed``): ``jax.lax.top_k`` is prefix-stable
    (``top_k(x, m)[:n] == top_k(x, n)`` for ``n <= m`` — ties break
    toward the lower index in both), so zeroing the IVF walk for probe
    ranks ``>= nprobe_t`` yields the EXACT candidate set a static
    ``nprobe=nprobe_t`` program produces, while the program shape stays
    keyed on the ``nprobe`` bucket.
    """
    B = s_cq.shape[0]
    with jax.named_scope("plaid.s1"):
        # (B, nq, np)
        _, cids = jax.lax.top_k(jnp.swapaxes(s_cq, 1, 2), nprobe)
    with jax.named_scope("plaid.cand"):
        cids = cids.reshape(B, -1)  # (B, nq*nprobe)
        starts = index.ivf_offsets[cids]
        lens = index.ivf_lens[cids]
        if nprobe_t is not None:
            # probe rank of each flattened (token, probe) slot; masked probes
            # get a zero-length IVF window -> contribute no pids at all
            nq = s_cq.shape[2]
            rank = jnp.tile(jnp.arange(nprobe, dtype=jnp.int32), nq)
            lens = jnp.where(rank[None, :] < nprobe_t, lens, 0)
        pos = jnp.arange(index.ivf_list_cap, dtype=jnp.int32)
        idx = starts[..., None] + pos[None, None, :]
        valid = pos[None, None, :] < lens[..., None]
        idx = jnp.where(valid, idx, 0)
        # pads are ``num_passages`` so they sort PAST every real pid through
        # the unique truncation (same reasoning as
        # ``plaid.candidate_generation`` — a -1 pad sorts first and evicts
        # the highest pid at a full cap)
        n = index.num_passages
        pids = jnp.where(valid, index.ivf_pids[idx], n)  # (B, nq*np, cap)
        dead_pids = None
        if alive is not None:
            real = pids < n
            safe = jnp.where(real, pids, 0)
            dead = real & ~alive[safe]
            dead_pids = jnp.where(dead, safe, n)  # raw pid where tombstoned
            pids = jnp.where(real & alive[safe], pids, n)
        uniq = jax.vmap(
            functools.partial(jnp.unique, size=candidate_cap, fill_value=n)
        )
        candidates = uniq(pids.reshape(B, -1))
        candidates = jnp.where(candidates < n, candidates, -1)
        if not with_stats:
            return candidates
        if dead_pids is None:
            alive_dropped = jnp.zeros(B, jnp.int32)
        else:
            uniq_dead = uniq(dead_pids.reshape(B, -1))
            alive_dropped = (uniq_dead < n).sum(axis=1).astype(jnp.int32)
        return candidates, alive_dropped


# --------------------------------------------------------------------------
# Shared candidate-token gather
# --------------------------------------------------------------------------
def gather_candidate_tokens_shared(
    index: PlaidIndex, candidates: jax.Array
) -> tuple[jax.Array, jax.Array]:
    """One code gather for the whole batch's candidate union.

    candidates: (B, cap) per-lane sorted unique pids (-1 pad).  The lanes'
    sets are merged into one sorted pool of static size B*cap (-1 remapped
    to ``_PAD_KEY`` so the pool stays sorted); each pooled passage's code
    window is gathered from HBM once, as two aligned W-slot rows shifted
    into place (``scoring.gather_doc_tokens``), then re-expanded per lane
    through the cheap int32 position map.  Candidates shared across lanes
    — the common case under correlated traffic — are fetched exactly once.

    Returns (codes (B, cap, L) with -1 pad, tok_valid (B, cap, L) bool),
    bitwise identical to per-lane ``scoring.gather_doc_tokens`` output.
    """
    B, cap = candidates.shape
    keyed = jnp.where(candidates >= 0, candidates, _PAD_KEY)
    pool = jnp.unique(keyed.reshape(-1), size=B * cap, fill_value=_PAD_KEY)
    pos = jnp.searchsorted(pool, keyed).astype(jnp.int32)  # (B, cap)
    pool_pids = jnp.where(pool != _PAD_KEY, pool, -1).astype(jnp.int32)
    codes_pool, tok_valid_pool = scoring.gather_doc_tokens(
        index.codes,
        index.doc_offsets,
        index.doc_lens,
        pool_pids,
        index.doc_maxlen,
        fill=-1,
    )
    return codes_pool[pos], tok_valid_pool[pos]


# --------------------------------------------------------------------------
# Stages 2-3 — batched centroid interaction (reference path)
# --------------------------------------------------------------------------
def centroid_interaction_batched(
    s_cq: jax.Array,  # (B, K, nq)
    codes: jax.Array,  # (B, nd, L) i32, -1 pad
    q_mask: jax.Array | None = None,  # (B, nq)
    keep_centroid: jax.Array | None = None,  # (B, K) bool
) -> jax.Array:
    """Batched ``scoring.centroid_interaction`` (same op order per lane,
    so results are bitwise identical to the vmap'd single-query path).
    Returns (B, nd) approximate scores."""
    B, nd, L = codes.shape
    valid = codes >= 0
    safe = jnp.where(valid, codes, 0)
    tok_scores = jnp.take_along_axis(
        s_cq, safe.reshape(B, nd * L, 1), axis=1
    ).reshape(B, nd, L, -1)  # (B, nd, L, nq)
    if keep_centroid is not None:
        kept = jnp.take_along_axis(
            keep_centroid, safe.reshape(B, nd * L), axis=1
        ).reshape(B, nd, L)
        valid = valid & kept
    tok_scores = jnp.where(
        valid[..., None], tok_scores, jnp.asarray(NEG, tok_scores.dtype)
    )
    per_q = tok_scores.max(axis=2).astype(jnp.float32)  # (B, nd, nq)
    per_q = jnp.maximum(per_q, 0.0)
    if q_mask is not None:
        per_q = per_q * q_mask[:, None, :]
    return per_q.sum(axis=-1)


# --------------------------------------------------------------------------
# Stage 4 — batched residual decompression + exact MaxSim (reference path)
# --------------------------------------------------------------------------
def decompress_score_batched(
    index: PlaidIndex,
    qs: jax.Array,  # (B, nq, d)
    q_masks: jax.Array,  # (B, nq)
    codes_blk: jax.Array,  # (B, nd, L) i32, -1 pad
    res_blk: jax.Array,  # (B, nd, L, pd) u8
    tok_valid: jax.Array,  # (B, nd, L) bool
) -> jax.Array:
    """Batched ``plaid.decompress_and_score_ref``: (B, nd) exact scores."""
    codec = index.codec
    safe = jnp.where(codes_blk >= 0, codes_blk, 0)
    emb = index.centroids[safe] + rc.decompress_residuals(codec, res_blk)
    scores = jnp.einsum(  # (B, nd, nq, L) — f32 on every backend
        "bqd,bntd->bnqt", qs, emb, precision=jax.lax.Precision.HIGHEST
    )
    scores = jnp.where(tok_valid[:, :, None, :], scores, NEG)
    per_q = scores.max(axis=-1)  # (B, nd, nq)
    per_q = per_q * q_masks[:, None, :]
    return per_q.sum(axis=-1)


# --------------------------------------------------------------------------
# Stages 1-3 — finalist selection (everything BEFORE residual payloads)
# --------------------------------------------------------------------------
def select_finalists_impl(
    index: PlaidIndex,
    qs: jax.Array,  # (B, nq, dim)
    q_masks: jax.Array,  # (B, nq)
    t_cs: jax.Array,  # TRACED: scalar or per-lane (B,) vector
    *,
    params,  # plaid.SearchParams (static; t_cs field ignored)
    diag: bool = False,
    funnel: bool = False,
    interpret: bool | None = None,
    alive: jax.Array | None = None,
    keep_blocks: bool = True,  # also return (codes4, tok_valid4) — the
    # per-finalist candidate blocks the UNFUSED stage 4 consumes; the fused
    # tail gathers CSR windows by pid, so fused callers pass False
    nprobe_t: jax.Array | None = None,  # TRACED effective caps <= the
    ndocs_t: jax.Array | None = None,  # static params.nprobe/ndocs (see
    # exec.bucketed: a cap grid reuses one program per pow2 bucket)
):
    """Stages 1-3 of the funnel: pick the (B, n3) finalist passages.

    This is the exact front of :func:`run_pipeline_impl`, split out because
    it is the part that touches ONLY device-tier state — stage-1 centroid
    scores, the IVF walk, and centroid-interaction over candidate codes.
    The residual payloads are never read, which is what lets the tiered
    engine (``core.tiered``) run this phase with host-resident payloads and
    pull just the finalists' CSR slices afterwards.

    Returns ``(final_pids, codes4, tok_valid4, extras)`` where ``extras``
    is a list holding the ``diag`` dict and/or ``FunnelStats`` when those
    flags are set (both are pure stage-1..3 reductions).
    """
    p = params
    B = qs.shape[0]
    if p.impl == "pallas":
        from repro.kernels import ops as K

        interaction = functools.partial(
            K.centroid_interaction_batched, interpret=interpret
        )
    else:
        interaction = centroid_interaction_batched

    # ---- Stage 1: one batched C.Q^T + per-lane candidate generation
    with jax.named_scope("plaid.s1"):
        s_cq = stage1_scores_batched(
            index, qs, p.score_dtype, p.stage1_dtype
        )  # (B, K, nq)
    cand_out = candidate_generation_batched(
        index, s_cq, p.nprobe, p.candidate_cap, alive, with_stats=funnel,
        nprobe_t=nprobe_t,
    )  # (B, cap); tombstoned passages never reach stage 2
    if funnel:
        candidates, alive_dropped = cand_out
        with jax.named_scope("plaid.cand"):
            # distinct centroids the top-nprobe probe touched: recomputes the
            # (tiny) stage-1 top_k, which XLA CSEs with candidate generation's
            _, cids_f = jax.lax.top_k(jnp.swapaxes(s_cq, 1, 2), p.nprobe)
            if nprobe_t is not None:
                # probes past the traced cap collapse onto each token's top-1
                # centroid so the distinct count matches a static nprobe_t run
                rank_f = jnp.arange(p.nprobe, dtype=jnp.int32)[None, None, :]
                cids_f = jnp.where(rank_f < nprobe_t, cids_f, cids_f[..., :1])
            cids_sorted = jnp.sort(cids_f.reshape(B, -1), axis=1)
            probed_centroids = (
                1 + (cids_sorted[:, 1:] != cids_sorted[:, :-1]).sum(axis=1)
            ).astype(jnp.int32)
    else:
        candidates = cand_out

    # ---- Stage 2: pruned centroid interaction over the shared gather
    with jax.named_scope("plaid.s2.gather"):
        codes_blk, tok_valid = gather_candidate_tokens_shared(
            index, candidates
        )
    with jax.named_scope("plaid.s2.score"):
        # t_cs may be a scalar (one threshold for the batch) or a per-lane
        # (B,) vector (the serving tier's per-request latency/quality knob);
        # either way it is traced, so value changes reuse the compiled
        # program.
        t_arr = jnp.asarray(t_cs)
        t_bcast = t_arr if t_arr.ndim == 0 else t_arr[:, None]  # vs (B, K)
        keep = scoring.prune_mask(s_cq, t_bcast)  # (B, K)
        approx2 = interaction(s_cq, codes_blk, q_masks, keep)  # (B, cap)
        approx2 = jnp.where(candidates >= 0, approx2, NEG)
        n2 = min(p.ndocs, p.candidate_cap)
        _, idx2 = jax.lax.top_k(approx2, n2)  # (B, n2)

    # ---- Stage 3: full centroid interaction on the survivors
    with jax.named_scope("plaid.s3"):
        codes3 = jnp.take_along_axis(codes_blk, idx2[..., None], axis=1)
        cand2 = jnp.take_along_axis(candidates, idx2, axis=1)
        if ndocs_t is not None:
            # Traced stage-2 cap: approx2's real entries are >= 0 and its
            # pads are NEG, so top_k's prefix stability means positions
            # < n2_t of idx2 are EXACTLY what a static ndocs=ndocs_t program
            # selects; masking the tail to -1 makes the survivor set
            # identical.
            nd_t = jnp.minimum(
                jnp.asarray(ndocs_t, jnp.int32), jnp.int32(p.candidate_cap)
            )
            rank2 = jnp.arange(n2, dtype=jnp.int32)[None, :]
            cand2 = jnp.where(rank2 < nd_t, cand2, -1)
        approx3 = interaction(s_cq, codes3, q_masks, None)
        approx3 = jnp.where(cand2 >= 0, approx3, NEG)
        n3 = min(max(p.ndocs // 4, p.k), n2)
        _, idx3 = jax.lax.top_k(approx3, n3)  # (B, n3)
        final_pids = jnp.take_along_axis(cand2, idx3, axis=1)  # (B, n3)
        if ndocs_t is not None:
            # stage-3 keeps max(ndocs // 4, k) of its n2 survivors — apply
            # the same rule at the traced cap (n3 >= n3_t always, so the
            # static top_k above already ordered the prefix identically)
            n3_t = jnp.minimum(
                jnp.maximum(
                    jnp.asarray(ndocs_t, jnp.int32) // 4, jnp.int32(p.k)
                ),
                nd_t,
            )
            rank3 = jnp.arange(n3, dtype=jnp.int32)[None, :]
            final_pids = jnp.where(rank3 < n3_t, final_pids, -1)

        if keep_blocks:
            codes4 = jnp.take_along_axis(codes3, idx3[..., None], axis=1)
            tok_valid3 = jnp.take_along_axis(
                tok_valid, idx2[..., None], axis=1
            )
            tok_valid4 = jnp.take_along_axis(
                tok_valid3, idx3[..., None], axis=1
            )
        else:
            codes4 = tok_valid4 = None

    extras = []
    if diag:
        extras.append(
            dict(
                stage1_candidates=(candidates >= 0).sum(axis=1),
                stage2_kept_centroids=keep.sum(axis=1),
                stage3_survivors=(final_pids >= 0).sum(axis=1),
            )
        )
    if funnel:
        extras.append(
            FunnelStats(
                probed_centroids=probed_centroids,
                stage1_candidates=(candidates >= 0)
                .sum(axis=1)
                .astype(jnp.int32),
                alive_dropped=alive_dropped,
                stage2_kept_centroids=keep.sum(axis=1).astype(jnp.int32),
                stage2_survivors=(cand2 >= 0).sum(axis=1).astype(jnp.int32),
                stage3_survivors=(final_pids >= 0)
                .sum(axis=1)
                .astype(jnp.int32),
                gathered_tokens=tok_valid.sum(axis=(1, 2)).astype(jnp.int32),
            )
        )
    return final_pids, codes4, tok_valid4, extras


# --------------------------------------------------------------------------
# Stage 4 — exact rescoring of the finalists + final top-k
# --------------------------------------------------------------------------
def exact_stage4_impl(
    index: PlaidIndex,
    qs: jax.Array,  # (B, nq, dim)
    q_masks: jax.Array,  # (B, nq)
    final_pids: jax.Array,  # (B, n3) pids INTO ``index``'s CSR arrays
    codes4: jax.Array | None,  # (B, n3, L) — required when not params.fused
    tok_valid4: jax.Array | None,  # (B, n3, L)
    *,
    params,
    interpret: bool | None = None,
) -> jax.Array:
    """Residual decompression + exact MaxSim over the finalists.

    The exact back of :func:`run_pipeline_impl`: the ONLY stage that reads
    ``index.residuals``.  ``final_pids`` indexes ``index``'s CSR arrays —
    the tiered engine passes a compacted candidate-slice index here with
    pool-local positions, and because both paths feed the same bytes
    through the same ops the scores are bitwise identical to the resident
    engine's.  Returns raw (B, n3) scores (padding lanes NOT yet masked;
    :func:`finalize_topk` applies the mask + top-k).
    """
    p = params
    B, n3 = final_pids.shape
    if p.fused:
        # Fused stage 3-5 tail: the finalists' CSR windows are gathered by
        # pid and scored by the stage-4 kernel — the decompressed f32
        # token tensor never materializes.
        if p.impl == "pallas":
            from repro.kernels import ops as K

            exact = K.gather_decompress_maxsim(
                qs,
                q_masks,
                final_pids,
                index.codes,
                index.residuals,
                index.doc_offsets,
                index.doc_lens,
                index.centroids,
                index.weights,
                nbits=index.nbits,
                doc_maxlen=index.doc_maxlen,
                interpret=interpret,
            )
        else:
            from repro.kernels import ref as kref

            exact = kref.gather_decompress_maxsim_ref(
                qs,
                q_masks,
                final_pids,
                index.codes,
                index.residuals,
                index.doc_offsets,
                index.doc_lens,
                index.centroids,
                index.weights,
                nbits=index.nbits,
                doc_maxlen=index.doc_maxlen,
            )
    else:
        if p.impl == "pallas":
            from repro.kernels import ops as K

            decompress_score = functools.partial(
                K.decompress_and_score_batched, interpret=interpret
            )
        else:
            decompress_score = None
        res_blk, _ = scoring.gather_doc_tokens(
            index.residuals,
            index.doc_offsets,
            index.doc_lens,
            final_pids.reshape(-1),
            index.doc_maxlen,
            fill=jnp.uint8(0),
        )  # one gather for all B*n3 finalists
        res_blk = res_blk.reshape(B, n3, index.doc_maxlen, -1)
        if decompress_score is None:
            exact = decompress_score_batched(
                index, qs, q_masks, codes4, res_blk, tok_valid4
            )
        else:
            exact = decompress_score(
                qs,
                q_masks,
                codes4,
                res_blk,
                tok_valid4,
                index.centroids,
                index.weights,
                nbits=index.nbits,
            )
    return exact


def finalize_topk(
    exact: jax.Array,  # (B, n3) raw stage-4 scores
    final_pids: jax.Array,  # (B, n3) GLOBAL pids (-1 pad)
    k: int,
) -> tuple[jax.Array, jax.Array]:
    """Mask padding lanes and take the final top-k over the finalists."""
    exact = jnp.where(final_pids >= 0, exact, NEG)
    kk = min(k, final_pids.shape[1])
    top_scores, idxk = jax.lax.top_k(exact, kk)  # (B, kk)
    top_pids = jnp.take_along_axis(final_pids, idxk, axis=1)
    return top_scores, top_pids


# --------------------------------------------------------------------------
# The pipeline driver — one jit entry point for B >= 1
# --------------------------------------------------------------------------
def run_pipeline_impl(
    index: PlaidIndex,
    qs: jax.Array,  # (B, nq, dim)
    q_masks: jax.Array,  # (B, nq)
    t_cs: jax.Array,  # TRACED: scalar or per-lane (B,) vector — changing
    # values never recompiles (switching scalar<->vector is one retrace)
    *,
    params,  # plaid.SearchParams (static; t_cs field ignored)
    diag: bool = False,
    funnel: bool = False,  # append an obs.FunnelStats aux output (static
    # flag: one extra compile the first time it is flipped, zero after)
    interpret: bool | None = None,  # Pallas mode; None = platform default
    alive: jax.Array | None = None,  # (Nd,) bool; False = tombstoned passage
    nprobe_t: jax.Array | None = None,  # TRACED effective nprobe/ndocs caps
    ndocs_t: jax.Array | None = None,  # (see exec.bucketed + select_finalists)
):
    """Unjitted pipeline body — composable under ``shard_map`` / outer jits
    (``engine_sharded`` runs this per shard).  Callers outside a tracing
    context use ``run_pipeline``.

    The body is the composition ``select_finalists_impl`` (stages 1-3) →
    ``exact_stage4_impl`` (residual rescore) → ``finalize_topk`` — the same
    ops in the same order as the historical monolithic pipeline, so outputs
    stay bitwise identical.  The split exists so ``core.tiered`` can run
    the two halves as separate programs with a host hop in between.

    ``funnel=True`` appends a :class:`repro.obs.funnel.FunnelStats` pytree
    of per-lane ``(B,)`` candidate counts at every funnel stage — cheap
    in-graph reductions over tensors the pipeline already materializes, so
    the instrumented program keeps the single stage-1 dot and the
    zero-retrace discipline (guarded in ``tests/test_obs.py``).

    ``alive`` is the live-index tombstone mask (``repro.live``): dead
    passages are nulled inside stage-1 candidate generation, BEFORE the
    ``candidate_cap`` truncation — a from-scratch rebuild of the surviving
    corpus would never have produced them (its IVF simply doesn't contain
    them), so every downstream stage sees the rebuild's candidates and
    tombstones don't eat cap slots under delete-heavy load.
    """
    global _N_TRACES
    _N_TRACES += 1
    final_pids, codes4, tok_valid4, extras = select_finalists_impl(
        index,
        qs,
        q_masks,
        t_cs,
        params=params,
        diag=diag,
        funnel=funnel,
        interpret=interpret,
        alive=alive,
        keep_blocks=not params.fused,
        nprobe_t=nprobe_t,
        ndocs_t=ndocs_t,
    )
    with jax.named_scope("plaid.s4"):
        exact = exact_stage4_impl(
            index,
            qs,
            q_masks,
            final_pids,
            codes4,
            tok_valid4,
            params=params,
            interpret=interpret,
        )
        top_scores, top_pids = finalize_topk(exact, final_pids, params.k)
    if extras:
        return (top_scores, top_pids, *extras)
    return top_scores, top_pids


run_pipeline_jit = jax.jit(
    run_pipeline_impl,
    static_argnames=("params", "diag", "funnel", "interpret"),
)


def run_pipeline(
    index: PlaidIndex,
    qs: jax.Array,
    q_masks: jax.Array,
    t_cs,
    params,
    *,
    diag: bool = False,
    funnel: bool = False,
    interpret: bool | None = None,
    alive: jax.Array | None = None,
    nprobe_t=None,
    ndocs_t=None,
):
    """The one compiled entry point for batched (B >= 1) PLAID search.

    qs: (B, nq, dim); q_masks: (B, nq).  Returns ((B, k) scores, (B, k)
    pids[, diagnostics dict of (B,) counters]).  ``params`` is a
    ``plaid.SearchParams`` (static: one compile per distinct cap/impl
    combination); its ``t_cs`` field is normalized out of the cache key —
    only the traced ``t_cs`` argument matters, so threshold sweeps are free.
    ``t_cs`` may be a scalar or a per-lane ``(B,)`` vector (per-request
    thresholds in one coalesced serving batch).
    ``alive`` is an optional traced (num_passages,) tombstone mask (see
    ``run_pipeline_impl``); updating tombstones never recompiles.
    ``funnel=True`` appends an ``obs.FunnelStats`` aux output (static flag:
    one extra compile when first flipped, zero retraces after).
    ``nprobe_t`` / ``ndocs_t`` are optional TRACED effective caps below the
    static ``params.nprobe`` / ``params.ndocs`` shape bounds — the pow2
    cap-bucketing machinery (``repro.exec.bucketed``) sweeps them with
    zero recompiles per bucket, and the masked result is identical to a
    static program built at those caps (``tests/test_eval.py``).
    """
    params = dataclasses.replace(params, t_cs=0.0)  # not a cache key
    if nprobe_t is not None:
        nprobe_t = jnp.asarray(nprobe_t, jnp.int32)
    if ndocs_t is not None:
        ndocs_t = jnp.asarray(ndocs_t, jnp.int32)
    return run_pipeline_jit(
        index,
        qs,
        q_masks,
        jnp.asarray(t_cs, jnp.float32),
        params=params,
        diag=diag,
        funnel=funnel,
        interpret=interpret,
        alive=alive,
        nprobe_t=nprobe_t,
        ndocs_t=ndocs_t,
    )
