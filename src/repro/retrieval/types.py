"""Facade types: parameters, requests, results, and the Retriever protocol.

The parameter model encodes the engine's compile discipline directly in the
API (PLAID reproducibility study: `nprobe`/`t_cs`/`ndocs` interactions
dominate the quality/latency tradeoff, so sweeps must be first-class):

* **static caps** — shape-determining; changing one compiles a new XLA
  program: ``k``, ``nprobe``, ``ndocs``, ``candidate_cap``, ``score_dtype``.
* **dynamic scalars** — traced operands; changing one reuses the compiled
  program: ``t_cs``.

Every backend documents which of these it honours via ``describe()``.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Protocol, runtime_checkable

from repro.constants import DEFAULT_CANDIDATE_CAP

#: Facade-wide default for the stage 1-3 approximate-score dtype.  One
#: documented default ("float32") shared by every backend; "bfloat16" is the
#: TPU bandwidth optimisation (see repro.core.scoring.centroid_scores).
DEFAULT_SCORE_DTYPE = "float32"

#: SearchParams fields that key the compile cache (recompile on change).
STATIC_FIELDS = (
    "k",
    "nprobe",
    "ndocs",
    "candidate_cap",
    "score_dtype",
    "stage1_dtype",
    "fused",
    "tiered",
)
#: SearchParams fields that are traced (no recompile on change).
DYNAMIC_FIELDS = ("t_cs",)


@dataclasses.dataclass(frozen=True)
class SearchParams:
    """Backend-agnostic search parameters (paper Table 2 + engine caps)."""

    # --- static caps: recompile on change -------------------------------
    k: int = 10
    nprobe: int = 1
    ndocs: int = 256
    #: C_max, the stage-1 candidate bound.  Single source of truth:
    #: ``repro.constants.DEFAULT_CANDIDATE_CAP`` (shared with the core
    #: engine's ``SearchParams`` and every ``params_for_k`` helper).
    candidate_cap: int = DEFAULT_CANDIDATE_CAP
    score_dtype: str = DEFAULT_SCORE_DTYPE
    #: Stage-1 ``C·Qᵀ`` operand dtype: "float32" | "bfloat16" | "int8"
    #: (the index's weight-only-quantized centroid table).  f32
    #: accumulation in every mode; stage 4 rescores exactly.
    stage1_dtype: str = "float32"
    #: Address the stage 3-5 tail by pid: gather the finalists' CSR windows
    #: and score them with the stage-4 kernel (rank-identical to the path
    #: fed by stage 2's blocks, which survives as the oracle).
    fused: bool = False
    #: Beyond-HBM storage mode: token payloads (packed residuals) stay
    #: host-resident (mmap) and only the finalists' CSR slices cross to the
    #: device per batch (``repro.core.tiered``).  Routes the ``"plaid"``
    #: family to the ``"plaid-tiered"`` backends at build time; results are
    #: bitwise rank-identical to the resident engine.
    tiered: bool = False
    # --- dynamic scalars: traced, swept freely at serve time ------------
    t_cs: float = 0.5

    def replace(self, **changes) -> "SearchParams":
        return dataclasses.replace(self, **changes)

    def static_key(self) -> tuple:
        """The compile-cache key: identical keys never recompile."""
        return tuple(getattr(self, f) for f in STATIC_FIELDS)

    def static_dict(self) -> dict:
        return {f: getattr(self, f) for f in STATIC_FIELDS}

    def dynamic_dict(self) -> dict:
        return {f: getattr(self, f) for f in DYNAMIC_FIELDS}

    def asdict(self) -> dict:
        return dataclasses.asdict(self)


#: Paper Table 2 settings, keyed by final k (facade mirror of
#: repro.core.plaid.PAPER_PARAMS).
PAPER_PARAMS = {
    10: SearchParams(k=10, nprobe=1, t_cs=0.5, ndocs=256),
    100: SearchParams(k=100, nprobe=2, t_cs=0.45, ndocs=1024),
    1000: SearchParams(k=1000, nprobe=4, t_cs=0.4, ndocs=4096),
}


def params_for_k(k: int, candidate_cap: int | None = None) -> SearchParams:
    """Paper Table 2 params for ``k``.  ``candidate_cap=None`` keeps the one
    documented default (``repro.constants.DEFAULT_CANDIDATE_CAP``) instead
    of the old silent 8192 override."""
    base = PAPER_PARAMS.get(k, SearchParams(k=k))
    if candidate_cap is None:
        candidate_cap = DEFAULT_CANDIDATE_CAP
    return base.replace(candidate_cap=candidate_cap)


@dataclasses.dataclass(frozen=True)
class RetrieverConfig:
    """Everything ``retrieval.build`` needs: backend choice + parameters.

    ``index`` is forwarded to the streaming index builder
    (``repro.build.build_index_streaming``): the classic knobs
    (``num_centroids``, ``nbits``, ``kmeans_iters``, ``seed``,
    ``ivf_list_cap``, frozen ``centroids``/``codec``) plus the streaming
    geometry (``chunk_docs``, ``sample_size``, ``n_devices``,
    ``stat_blocks``).  ``n_shards`` applies to the device-sharded backends
    (``"plaid-sharded"`` and the ``"live-sharded"`` family); ``None``
    means one shard per local device.
    """

    backend: str = "plaid"
    params: SearchParams = SearchParams()
    n_shards: int | None = None
    index: dict = dataclasses.field(default_factory=dict)

    def replace(self, **changes) -> "RetrieverConfig":
        return dataclasses.replace(self, **changes)


@dataclasses.dataclass
class SearchRequest:
    """One search call: a query (or batch) plus per-request dynamic knobs.

    ``t_cs`` and ``k`` are the per-request latency/quality SLO knobs the
    serving tier (``repro.serving``) exposes: ``t_cs`` rides through the
    coalesced batch as a traced per-lane scalar (never recompiles) and
    ``k`` is served by max-``k`` dispatch + per-request truncation (the
    batch runs at the retriever's compiled ``params.k``; a request's
    ``k`` must not exceed it).  ``priority`` / ``deadline_ms`` feed the
    serving tier's admission control: two-level priority queues
    ("interactive" ahead of "batch") and expiry-before-dispatch.  Direct
    ``Retriever.search*`` calls ignore the serving-only fields.
    """

    q: Any  # (nq, dim) single query matrix, or (B, nq, dim) batch
    q_mask: Any | None = None  # (nq,) / (B, nq); None = all tokens valid
    t_cs: float | None = None  # dynamic override — never recompiles
    with_diagnostics: bool = False  # per-stage survivor counts (one extra
    # compile the first time it is flipped; static flag)
    with_funnel: bool = False  # attach obs.FunnelStats funnel telemetry
    # (static flag like with_diagnostics: one extra compile when first
    # flipped, zero retraces after; merged across partitions/segments)
    # --- serving-tier per-request knobs (repro.serving) -----------------
    k: int | None = None  # truncate the result to k <= retriever params.k
    priority: str = "interactive"  # admission class: "interactive" | "batch"
    deadline_ms: float | None = None  # relative deadline; expired requests
    # are failed with DeadlineExceeded instead of dispatched

    @property
    def batched(self) -> bool:
        return getattr(self.q, "ndim", 0) == 3


@dataclasses.dataclass
class SearchResult:
    """Top-k result plus serving metadata.

    Iterable as ``(scores, pids)`` so call sites migrating from the raw
    engine tuples keep working: ``scores, pids = retriever.search(q)``.
    """

    scores: Any  # (k,) or (B, k)
    pids: Any  # (k,) or (B, k) int32
    backend: str
    k: int
    latency_ms: float | None = None
    t_cs: float | None = None  # the dynamic threshold this search ran with
    diagnostics: dict | None = None  # per-stage survivor counts (if requested)
    funnel: dict | None = None  # obs.FunnelStats as host arrays (if
    # requested via with_funnel): per-query candidate counts at every
    # funnel stage, merged across partitions for sharded/live backends

    def __iter__(self):
        return iter((self.scores, self.pids))

    def topk(self):
        return self.scores, self.pids


@runtime_checkable
class Retriever(Protocol):
    """The one engine API: everything serving/benchmarks/examples consume.

    Implementations are registered by name ("vanilla", "plaid",
    "plaid-pallas", "plaid-sharded", ...) in ``repro.retrieval.registry``;
    construct them via ``retrieval.build`` / ``retrieval.from_index`` /
    ``retrieval.load``.
    """

    backend_name: str
    params: SearchParams

    def search(
        self,
        q: Any,
        q_mask: Any | None = None,
        *,
        t_cs: float | None = None,
        with_diagnostics: bool = False,
    ) -> SearchResult:
        """One query matrix (nq, dim) -> top-k SearchResult."""
        ...

    def search_batch(
        self,
        qs: Any,
        q_masks: Any | None = None,
        *,
        t_cs: float | None = None,
        with_diagnostics: bool = False,
    ) -> SearchResult:
        """Query batch (B, nq, dim) -> batched top-k SearchResult."""
        ...

    def save(self, path: str) -> None:
        """Persist index + retriever metadata; ``retrieval.load`` restores."""
        ...

    def describe(self) -> dict:
        """Static-shape / compile-cache introspection + index stats."""
        ...


@runtime_checkable
class MutableRetriever(Retriever, Protocol):
    """A Retriever whose corpus can change at serving time.

    Implemented by the ``"live"`` / ``"live-pallas"`` backends and their
    device-sharded composition ``"live-sharded"`` /
    ``"live-sharded-pallas"`` (``repro.live`` + ``repro.exec``): mutations
    are snapshot-consistent with in-flight searches and never require an
    index rebuild.  ``BatchingServer`` forwards its ``add_passages`` /
    ``delete_passages`` to this surface.

    Mutable backends additionally expose a monotonic ``generation``
    property (the LiveIndex mutation counter): the serving tier's result
    cache stamps entries with it, so ingest/delete/compaction invalidate
    cached results atomically (one integer compare, no scan).
    """

    def add_passages(self, doc_embeddings, doc_lens=None):
        """Ingest passages (one delta segment); returns their global pids."""
        ...

    def delete_passages(self, pids) -> int:
        """Tombstone global pids; returns how many were newly deleted."""
        ...

    def compact(self):
        """Merge delta segments into the base, dropping tombstoned docs;
        returns the old->new global pid map (``-1`` = dropped)."""
        ...
