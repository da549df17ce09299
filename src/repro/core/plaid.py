"""The PLAID 4-stage scoring pipeline (paper Fig. 5), as one jit program.

Stage 1  candidate generation: top-``nprobe`` centroids per query token ->
         union of passages from the centroid->pid inverted lists.
Stage 2  *pruned* centroid interaction (threshold ``t_cs``) -> top ``ndocs``.
Stage 3  full centroid interaction -> top ``ndocs // 4``.
Stage 4  residual decompression + exact MaxSim -> final top-``k``.

Static-shape discipline (DESIGN §7): candidate sets are padded to
``candidate_cap`` with ``-1`` sentinels; all per-stage shapes are compile-time
constants so the whole pipeline is a single fused XLA program that also
lowers for sharded execution (one shard = one sub-corpus).

Parameter discipline: shape-determining caps (``k``, ``nprobe``, ``ndocs``,
``candidate_cap``) and codegen choices (``impl``, ``score_dtype``) are
compile-time static; the pruning threshold ``t_cs`` is a TRACED scalar, so a
serving process can tune pruning aggressiveness per request without paying a
new XLA compile (the public knob lives in ``repro.retrieval``).
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp

from repro.constants import DEFAULT_CANDIDATE_CAP
from repro.core import pipeline
from repro.core import residual_codec as rc
from repro.core import scoring
from repro.core.index import PlaidIndex

NEG = scoring.NEG


@dataclasses.dataclass(frozen=True)
class SearchParams:
    """Hyperparameters (paper Table 2) + static engine caps."""

    k: int = 10
    nprobe: int = 1
    t_cs: float = 0.5
    ndocs: int = 256
    #: C_max: static bound on |stage-1 candidates|.  The single source of
    #: truth is ``repro.constants.DEFAULT_CANDIDATE_CAP`` — this default,
    #: the facade's ``retrieval.SearchParams``, and every ``params_for_k``
    #: helper all derive from it (they used to disagree: 4096 here vs a
    #: silent 8192 override in ``params_for_k``; 8192 won — see the
    #: constant's rationale).  Always clamped to the corpus size at engine
    #: construction.
    candidate_cap: int = DEFAULT_CANDIDATE_CAP
    impl: str = "ref"  # "ref" (pure jnp) | "pallas" (platform-aware kernels)
    score_dtype: str = "float32"  # stage 1-3 approximate-score dtype. §Perf
    # S2: "bfloat16" halves score-matrix + gather traffic on TPU with no
    # measured recall change; default stays f32 (everywhere, including
    # ``_search``) because the CPU dry-run metric can't see the win (bf16
    # emulation inserts f32 copies).
    stage1_dtype: str = "float32"  # stage-1 C·Qᵀ OPERAND dtype: "float32" |
    # "bfloat16" (casted operands) | "int8" (quantized centroid table,
    # ``index.centroids_q``).  Accumulation is always f32; under lossless
    # caps (nprobe=K, cap >= corpus) final ranks are identical because
    # stage 4 rescores exactly.  Distinct from ``score_dtype``, which sets
    # the stage 1-3 approximate-SCORE storage dtype.
    fused: bool = False  # stage 3-5 tail addressed by pid: CSR windows
    # gathered and scored by the stage-4 kernel (repro.kernels.fused_score)
    # instead of routing stage 2's blocks; rank-identical, the unfused
    # path survives as the equivalence oracle.

    def stage3_docs(self) -> int:
        return max(self.ndocs // 4, self.k)


#: Paper Table 2 settings, keyed by final k.
PAPER_PARAMS = {
    10: SearchParams(k=10, nprobe=1, t_cs=0.5, ndocs=256),
    100: SearchParams(k=100, nprobe=2, t_cs=0.45, ndocs=1024),
    1000: SearchParams(k=1000, nprobe=4, t_cs=0.4, ndocs=4096),
}


def params_for_k(k: int, candidate_cap: int | None = None, impl: str = "ref"):
    """Paper Table 2 params for ``k``.  ``candidate_cap=None`` keeps the
    one documented default (``repro.constants.DEFAULT_CANDIDATE_CAP``)."""
    base = PAPER_PARAMS.get(k, SearchParams(k=k))
    if candidate_cap is None:
        candidate_cap = DEFAULT_CANDIDATE_CAP
    return dataclasses.replace(base, candidate_cap=candidate_cap, impl=impl)


def clamp_params(params: SearchParams, n_passages: int) -> SearchParams:
    """Corpus-clamped static caps — THE clamp rule, shared by every
    whole-corpus pipeline consumer (``PlaidEngine`` per index,
    ``repro.live.LiveEngine`` per segment) so they cannot diverge.  The
    document-sharded engine intentionally does NOT clamp ``ndocs`` (see
    ``engine_sharded.make_sharded_search``)."""
    cap = min(params.candidate_cap, max(n_passages, 2))
    return dataclasses.replace(
        params, candidate_cap=cap, ndocs=min(params.ndocs, cap)
    )


# --------------------------------------------------------------------------
# Stage 1 — candidate generation
# --------------------------------------------------------------------------
def candidate_generation(
    index: PlaidIndex, s_cq: jax.Array, nprobe: int, candidate_cap: int
) -> jax.Array:
    """Return (candidate_cap,) sorted unique passage ids, -1 pads at the
    tail.  Pads are ``num_passages`` (past every real pid) through the
    sorted-unique truncation so they can never displace a real candidate —
    a -1 pad sorts FIRST and would silently evict the highest pid whenever
    the unique count reaches the cap, making ``candidate_cap =
    num_passages`` lossy by exactly one passage."""
    nq = s_cq.shape[1]
    n = index.num_passages
    # top-nprobe centroids per query token (scores are (K, nq))
    _, cids = jax.lax.top_k(s_cq.T, nprobe)  # (nq, nprobe)
    cids = cids.reshape(-1)  # (nq*nprobe,)
    starts = index.ivf_offsets[cids]  # (nq*nprobe,)
    lens = index.ivf_lens[cids]
    pos = jnp.arange(index.ivf_list_cap, dtype=jnp.int32)
    idx = starts[:, None] + pos[None, :]
    valid = pos[None, :] < lens[:, None]
    idx = jnp.where(valid, idx, 0)
    pids = jnp.where(valid, index.ivf_pids[idx], n)  # (nq*nprobe, cap)
    cand = jnp.unique(pids.reshape(-1), size=candidate_cap, fill_value=n)
    return jnp.where(cand < n, cand, -1)


# --------------------------------------------------------------------------
# Stage 4 — decompress + exact MaxSim (reference path)
# --------------------------------------------------------------------------
def decompress_and_score_ref(
    index: PlaidIndex,
    q: jax.Array,  # (nq, dim)
    q_mask: jax.Array,  # (nq,)
    codes_blk: jax.Array,  # (nd, L) i32, -1 pad
    res_blk: jax.Array,  # (nd, L, packed_dim) u8
    tok_valid: jax.Array,  # (nd, L) bool
) -> jax.Array:
    codec = index.codec
    safe = jnp.where(codes_blk >= 0, codes_blk, 0)
    emb = index.centroids[safe] + rc.decompress_residuals(codec, res_blk)
    return scoring.maxsim(q, emb, q_mask=q_mask, d_mask=tok_valid)


# --------------------------------------------------------------------------
# Full pipeline (single query matrix)
# --------------------------------------------------------------------------
_N_TRACES = 0  # incremented at trace time; one retrace == one XLA compile.
# ``repro.retrieval`` exposes this via ``describe()`` so tests and serving
# dashboards can assert that dynamic-parameter sweeps hit the compile cache.


def trace_count() -> int:
    """Total (re)traces/compiles of the search path: the batched pipeline
    (``core.pipeline.run_pipeline``, the serving entry point) plus the
    legacy single-query ``_search`` oracle."""
    return _N_TRACES + pipeline.trace_count()


@functools.partial(
    jax.jit,
    static_argnames=(
        "k", "nprobe", "ndocs", "candidate_cap", "impl", "score_dtype", "diag",
    ),
)
def _search(
    index: PlaidIndex,
    q: jax.Array,
    q_mask: jax.Array,
    s_cq: jax.Array | None = None,  # precomputed (K, nq) stage-1 scores —
    # batched engines compute C.Q^T ONCE for all queries (§Perf S1: the
    # centroid matrix is read once per batch instead of once per query)
    t_cs: jax.Array | float = 0.5,  # TRACED: changing it never recompiles
    *,
    k: int,
    nprobe: int,
    ndocs: int,
    candidate_cap: int,
    impl: str,
    score_dtype: str = "float32",
    diag: bool = False,
):
    global _N_TRACES
    _N_TRACES += 1
    if impl == "pallas":
        from repro.kernels import ops as K

        # interpret mode is platform-aware (repro.kernels.dispatch):
        # interpreter off-TPU, Mosaic lowering on TPU.
        interaction = K.centroid_interaction
        decompress_score = K.decompress_and_score
    else:
        interaction = scoring.centroid_interaction
        decompress_score = None

    # ---- Stage 1: query-centroid scores + candidate generation
    if s_cq is None:
        s_cq = scoring.centroid_scores(
            q, index.centroids, dtype=jnp.dtype(score_dtype)
        )  # (K, nq)
    else:
        s_cq = s_cq.astype(jnp.dtype(score_dtype))
    candidates = candidate_generation(index, s_cq, nprobe, candidate_cap)

    # ---- Stage 2: pruned centroid interaction
    keep = scoring.prune_mask(s_cq, t_cs)  # (K,)
    codes_blk, tok_valid = scoring.gather_doc_tokens(
        index.codes,
        index.doc_offsets,
        index.doc_lens,
        candidates,
        index.doc_maxlen,
        fill=-1,
    )
    approx2 = interaction(s_cq, codes_blk, q_mask=q_mask, keep_centroid=keep)
    approx2 = jnp.where(candidates >= 0, approx2, NEG)
    n2 = min(ndocs, candidate_cap)
    _, idx2 = jax.lax.top_k(approx2, n2)

    # ---- Stage 3: full centroid interaction on the survivors
    codes3 = codes_blk[idx2]
    approx3 = interaction(s_cq, codes3, q_mask=q_mask, keep_centroid=None)
    approx3 = jnp.where(candidates[idx2] >= 0, approx3, NEG)
    n3 = min(max(ndocs // 4, k), n2)
    _, idx3 = jax.lax.top_k(approx3, n3)
    final_pids = candidates[idx2][idx3]  # (n3,)

    # ---- Stage 4: residual decompression + exact MaxSim
    codes4 = codes3[idx3]
    tok_valid4 = tok_valid[idx2][idx3]
    res_blk, _ = scoring.gather_doc_tokens(
        index.residuals,
        index.doc_offsets,
        index.doc_lens,
        final_pids,
        index.doc_maxlen,
        fill=jnp.uint8(0),
    )
    if decompress_score is None:
        exact = decompress_and_score_ref(
            index, q, q_mask, codes4, res_blk, tok_valid4
        )
    else:
        exact = decompress_score(
            q,
            q_mask,
            codes4,
            res_blk,
            tok_valid4,
            index.centroids,
            index.weights,
            nbits=index.nbits,
        )
    exact = jnp.where(final_pids >= 0, exact, NEG)
    kk = min(k, n3)
    top_scores, idxk = jax.lax.top_k(exact, kk)
    if diag:
        diagnostics = dict(
            stage1_candidates=(candidates >= 0).sum(),
            stage2_kept_centroids=keep.sum(),
            stage3_survivors=(final_pids >= 0).sum(),
        )
        return top_scores, final_pids[idxk], diagnostics
    return top_scores, final_pids[idxk]


class PlaidEngine:
    """Internal engine handle over one in-memory index.

    The public, backend-agnostic API is ``repro.retrieval``; this class is
    the implementation the ``"plaid"`` / ``"plaid-pallas"`` backends wrap.
    ``search``/``search_batch`` return raw ``(scores, pids)`` tuples.

    Both entry points run the batch-first ``core.pipeline`` program —
    ``search`` is the B=1 squeeze of ``search_batch``, not a separate code
    path.  (The pre-refactor vmap-of-``_search`` path lives on only as a
    locally-defined reference in ``tests/test_pipeline.py``.)
    """

    def __init__(self, index: PlaidIndex, params: SearchParams | None = None):
        self.index = index
        self.params = params or SearchParams()

    def _pipeline_params(self) -> SearchParams:
        """Corpus-clamped static params (``clamp_params``) — both the
        pipeline and the ``_search`` oracle derive from this, so they
        cannot diverge."""
        return clamp_params(self.params, self.index.num_passages)

    def _kwargs(self):
        """Static (compile-cache-keyed) kwargs; ``t_cs`` is passed per call."""
        p = self._pipeline_params()
        return dict(
            k=p.k,
            nprobe=p.nprobe,
            ndocs=p.ndocs,
            candidate_cap=p.candidate_cap,
            impl=p.impl,
            score_dtype=p.score_dtype,
        )

    def search(
        self,
        q: jax.Array,
        q_mask: jax.Array | None = None,
        *,
        t_cs: float | None = None,
        diag: bool = False,
        funnel: bool = False,
        interpret: bool | None = None,
    ):
        """q: (nq, dim) one query matrix -> (scores (k,), pids (k,))."""
        if q_mask is None:
            q_mask = jnp.ones(q.shape[0], jnp.float32)
        t = self.params.t_cs if t_cs is None else t_cs
        out = pipeline.run_pipeline(
            self.index,
            q[None],
            q_mask[None],
            t,
            self._pipeline_params(),
            diag=diag,
            funnel=funnel,
            interpret=interpret,
        )
        scores, pids, *extras = out
        out_extras = []
        if diag:
            diagnostics = extras.pop(0)
            out_extras.append({k: v[0] for k, v in diagnostics.items()})
        if funnel:
            fs = extras.pop(0)
            out_extras.append(type(fs)(*(v[0] for v in fs)))
        if out_extras:
            return (scores[0], pids[0], *out_extras)
        return scores[0], pids[0]

    def search_batch(
        self,
        qs: jax.Array,
        q_masks: jax.Array | None = None,
        *,
        t_cs: float | None = None,
        diag: bool = False,
        funnel: bool = False,
        interpret: bool | None = None,
    ):
        """qs: (B, nq, dim) -> (scores (B, k), pids (B, k))."""
        if q_masks is None:
            q_masks = jnp.ones(qs.shape[:2], jnp.float32)
        t = self.params.t_cs if t_cs is None else t_cs
        return pipeline.run_pipeline(
            self.index,
            qs,
            q_masks,
            t,
            self._pipeline_params(),
            diag=diag,
            funnel=funnel,
            interpret=interpret,
        )

