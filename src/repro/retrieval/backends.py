"""The static built-in backends behind the ``Retriever`` facade.

====================  =====================================================
``vanilla``           ColBERTv2 baseline (embedding-level IVF, full padded
                      decompression).  No dynamic parameters.
``plaid``             PLAID 4-stage pipeline, reference (pure-jnp) kernels.
``plaid-pallas``      Same pipeline through the Pallas kernels (interpret
                      mode on CPU; Mosaic lowering on TPU).
``plaid-sharded``     Document-sharded PLAID under ``shard_map`` (one shard
                      per mesh device, small all-gather top-k merge).
``plaid-tiered``      Beyond-HBM PLAID: host-resident (mmap) token
                      payloads, per-batch candidate-slice gather
                      (``repro.core.tiered`` / ``repro.exec.tiered``).
                      ``SearchParams(tiered=True)`` routes the plaid
                      family here automatically.
``plaid-tiered-pallas``  Tiered with the Pallas stage kernels (the fused
                      tail runs over the compacted slice arrays).
====================  =====================================================

The mutable-corpus backends (``"live"`` / ``"live-pallas"`` /
``"live-sharded"`` / ``"live-sharded-pallas"``, implementing the
``MutableRetriever`` protocol) register from ``repro.live.backend``,
which reuses this module's request/result plumbing.

Parameter mapping is uniform: ``SearchParams.candidate_cap`` is the stage-1
candidate bound (candidate *passages* for PLAID, candidate *embeddings* for
vanilla, matching each engine's native unit) and ``ndocs`` the stage-2/final
passage bound.  ``t_cs`` is traced on the PLAID backends — sweeping it at
serve time never recompiles (``describe()["compile"]`` proves it).
"""
from __future__ import annotations

import contextlib
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.compat import make_mesh
from repro.core import engine_sharded
from repro.core import indexer
from repro.core import pipeline as pipeline_mod
from repro.core import plaid as plaid_mod
from repro.core import vanilla as vanilla_mod
from repro.obs.trace import get_tracer
from repro.retrieval import registry
from repro.retrieval.types import (
    DYNAMIC_FIELDS,
    RetrieverConfig,
    SearchParams,
    SearchRequest,
    SearchResult,
    STATIC_FIELDS,
)


def _build_index(corpus_embs, cfg: RetrieverConfig, doc_lens):
    """Every facade ``build`` routes through the streaming two-pass builder
    (``repro.build``): bounded host memory, mesh-parallel pass 1, and the
    same keyword surface as the monolithic ``build_index`` plus the
    streaming knobs (``chunk_docs``, ``sample_size``, ``n_devices``,
    ``stat_blocks``) via ``RetrieverConfig.index``."""
    from repro.build import build_index_streaming

    return build_index_streaming(corpus_embs, doc_lens=doc_lens, **cfg.index)


def to_engine_params(p: SearchParams, impl: str = "ref") -> plaid_mod.SearchParams:
    """Facade ``SearchParams`` -> core ``plaid.SearchParams``.

    The ONE mapping site shared by every PLAID-pipeline backend (plaid,
    plaid-pallas, plaid-sharded, live, live-pallas): adding a field to the
    facade params only needs threading here."""
    return plaid_mod.SearchParams(
        k=p.k,
        nprobe=p.nprobe,
        t_cs=p.t_cs,
        ndocs=p.ndocs,
        candidate_cap=p.candidate_cap,
        impl=impl,
        score_dtype=p.score_dtype,
        stage1_dtype=p.stage1_dtype,
        fused=p.fused,
    )


def _as_request(q, q_mask, t_cs, with_diagnostics, with_funnel=False):
    if isinstance(q, SearchRequest):
        return q
    return SearchRequest(
        q=q, q_mask=q_mask, t_cs=t_cs, with_diagnostics=with_diagnostics,
        with_funnel=with_funnel,
    )


def _reject_diagnostics(req: SearchRequest, backend: str) -> None:
    if req.with_diagnostics:
        raise ValueError(
            f"with_diagnostics is not supported by backend {backend!r} "
            "(per-stage survivor counts exist on 'plaid'/'plaid-pallas')"
        )


def _reject_funnel(req: SearchRequest, backend: str) -> None:
    if getattr(req, "with_funnel", False):
        raise ValueError(
            f"with_funnel is not supported by backend {backend!r} "
            "(funnel telemetry exists on the PLAID-pipeline backends)"
        )


def _finish(
    out, *, backend, k, t_cs, t0, diag_names=None, funnel=False,
    tracer=None,
) -> SearchResult:
    """Block on device results and wrap them with serving metadata.

    Blocking is part of the facade contract: ``SearchResult.latency_ms``
    measures a completed search.  Callers that want async dispatch and
    device/host overlap (request pipelining) use the core engines, which
    return unblocked device arrays.  With a ``tracer`` the wait is a
    ``retrieval.block`` span."""
    scores, pids, *extras = out
    diagnostics = funnel_stats = None
    if diag_names is not None:
        diagnostics = extras.pop(0)
        diagnostics = {name: diagnostics[name] for name in diag_names}
    if funnel:
        funnel_stats = extras.pop(0)
    block = tracer.span("retrieval.block") if tracer else None
    with block or contextlib.nullcontext():
        jax.block_until_ready(pids)
    latency_ms = (time.perf_counter() - t0) * 1e3
    if diagnostics is not None:
        diagnostics = {
            name: np.asarray(v) if np.ndim(v) else int(v)
            for name, v in diagnostics.items()
        }
    if funnel_stats is not None:
        funnel_stats = {
            name: np.asarray(v) if np.ndim(v) else int(v)
            for name, v in zip(type(funnel_stats)._fields, funnel_stats)
        }
    return SearchResult(
        scores=scores,
        pids=pids,
        backend=backend,
        k=k,
        latency_ms=latency_ms,
        t_cs=t_cs,
        diagnostics=diagnostics,
        funnel=funnel_stats,
    )


_DIAG_NAMES = ("stage1_candidates", "stage2_kept_centroids", "stage3_survivors")


# --------------------------------------------------------------------------
# PLAID family (single-host): "plaid" and "plaid-pallas"
# --------------------------------------------------------------------------
@registry.register("plaid")
class PlaidRetriever:
    """Single-host PLAID engine behind the facade."""

    impl = "ref"

    def __init__(self, index, params: SearchParams | None = None):
        self.index = index
        self.params = params or SearchParams()
        self._engine = plaid_mod.PlaidEngine(
            index, to_engine_params(self.params, self.impl)
        )

    # ---- construction ----------------------------------------------------
    @classmethod
    def build(cls, corpus_embs, cfg: RetrieverConfig, doc_lens=None):
        return cls(_build_index(corpus_embs, cfg, doc_lens), cfg.params)

    @classmethod
    def from_index(cls, index, cfg: RetrieverConfig):
        return cls(index, cfg.params)

    @classmethod
    def load(cls, path: str, params: SearchParams | None = None):
        return cls(indexer.load_index(path), params)

    def save(self, path: str) -> None:
        indexer.save_index(path, self.index)
        registry.write_meta(path, self)

    # ---- search ----------------------------------------------------------
    def search(self, q, q_mask=None, *, t_cs=None, with_diagnostics=False,
               with_funnel=False):
        req = _as_request(q, q_mask, t_cs, with_diagnostics, with_funnel)
        t = self.params.t_cs if req.t_cs is None else req.t_cs
        t0 = time.perf_counter()
        out = self._engine.search(
            req.q, req.q_mask, t_cs=t, diag=req.with_diagnostics,
            funnel=req.with_funnel,
        )
        return _finish(
            out,
            backend=self.backend_name,
            k=self.params.k,
            t_cs=t,
            t0=t0,
            diag_names=_DIAG_NAMES if req.with_diagnostics else None,
            funnel=req.with_funnel,
        )

    def search_batch(self, qs, q_masks=None, *, t_cs=None,
                     with_diagnostics=False, with_funnel=False):
        """Spans, into the process's tracer: ``retrieval.search_batch``
        around the call; in it ``retrieval.launch`` (request packing, the
        engine, the program's dispatch) until the engine returns unblocked
        arrays, then ``retrieval.block`` until they are ready."""
        tracer = get_tracer()
        with tracer.span("retrieval.search_batch", backend=self.backend_name):
            with tracer.span("retrieval.launch"):
                req = _as_request(
                    qs, q_masks, t_cs, with_diagnostics, with_funnel
                )
                t = self.params.t_cs if req.t_cs is None else req.t_cs
                t0 = time.perf_counter()
                out = self._engine.search_batch(
                    req.q, req.q_mask, t_cs=t, diag=req.with_diagnostics,
                    funnel=req.with_funnel,
                )
            return _finish(
                out,
                backend=self.backend_name,
                k=self.params.k,
                t_cs=t,
                t0=t0,
                diag_names=_DIAG_NAMES if req.with_diagnostics else None,
                funnel=req.with_funnel,
                tracer=tracer,
            )

    # ---- introspection ---------------------------------------------------
    def describe(self) -> dict:
        effective = self._engine._kwargs()
        return dict(
            backend=self.backend_name,
            impl=self.impl,
            static=self.params.static_dict(),
            static_effective=effective,  # caps after clamping to the corpus
            dynamic=self.params.dynamic_dict(),
            static_fields=STATIC_FIELDS,
            dynamic_fields=DYNAMIC_FIELDS,
            index=dict(
                num_passages=self.index.num_passages,
                num_tokens=self.index.num_tokens,
                num_centroids=self.index.num_centroids,
                dim=self.index.dim,
                nbits=self.index.nbits,
                doc_maxlen=self.index.doc_maxlen,
            ),
            compile=dict(
                trace_count=plaid_mod.trace_count(),
                cache_size=(
                    pipeline_mod.run_pipeline_jit._cache_size()
                    + plaid_mod._search._cache_size()
                ),
            ),
        )


@registry.register("plaid-pallas")
class PlaidPallasRetriever(PlaidRetriever):
    """PLAID through the Pallas kernels (interpret on CPU, Mosaic on TPU)."""

    impl = "pallas"


# --------------------------------------------------------------------------
# Vanilla ColBERTv2 baseline
# --------------------------------------------------------------------------
@registry.register("vanilla")
class VanillaRetriever:
    """ColBERTv2 baseline behind the facade.  No dynamic parameters
    (``t_cs`` overrides are accepted and ignored — the pipeline has no
    pruning stage)."""

    def __init__(self, index, params: SearchParams | None = None):
        self.index = index
        self.params = params or SearchParams()
        p = self.params
        self._engine = vanilla_mod.VanillaEngine(
            index,
            vanilla_mod.VanillaParams(
                k=p.k,
                nprobe=p.nprobe,
                ncandidates=p.candidate_cap,
                ndocs_cap=p.ndocs,
            ),
        )

    @classmethod
    def build(cls, corpus_embs, cfg: RetrieverConfig, doc_lens=None):
        return cls(_build_index(corpus_embs, cfg, doc_lens), cfg.params)

    @classmethod
    def from_index(cls, index, cfg: RetrieverConfig):
        return cls(index, cfg.params)

    @classmethod
    def load(cls, path: str, params: SearchParams | None = None):
        return cls(indexer.load_index(path), params)

    def save(self, path: str) -> None:
        indexer.save_index(path, self.index)
        registry.write_meta(path, self)

    def search(self, q, q_mask=None, *, t_cs=None, with_diagnostics=False,
               with_funnel=False):
        req = _as_request(q, q_mask, t_cs, with_diagnostics, with_funnel)
        _reject_diagnostics(req, self.backend_name)
        _reject_funnel(req, self.backend_name)
        t0 = time.perf_counter()
        out = self._engine.search(req.q, req.q_mask)
        return _finish(
            out, backend=self.backend_name, k=self.params.k, t_cs=None, t0=t0
        )

    def search_batch(self, qs, q_masks=None, *, t_cs=None,
                     with_diagnostics=False, with_funnel=False):
        req = _as_request(qs, q_masks, t_cs, with_diagnostics, with_funnel)
        _reject_diagnostics(req, self.backend_name)
        _reject_funnel(req, self.backend_name)
        t0 = time.perf_counter()
        out = self._engine.search_batch(req.q, req.q_mask)
        return _finish(
            out, backend=self.backend_name, k=self.params.k, t_cs=None, t0=t0
        )

    def describe(self) -> dict:
        return dict(
            backend=self.backend_name,
            static=self.params.static_dict(),
            static_effective=self._engine._kwargs(),
            dynamic={},
            static_fields=STATIC_FIELDS,
            dynamic_fields=(),  # vanilla has no traced knobs
            index=dict(
                num_passages=self.index.num_passages,
                num_tokens=self.index.num_tokens,
                num_centroids=self.index.num_centroids,
                dim=self.index.dim,
                nbits=self.index.nbits,
                doc_maxlen=self.index.doc_maxlen,
            ),
        )


# --------------------------------------------------------------------------
# Document-sharded PLAID
# --------------------------------------------------------------------------
def _default_mesh():
    return make_mesh((len(jax.devices()),), ("data",))


@registry.register("plaid-sharded")
class ShardedRetriever:
    """Document-sharded PLAID: one shard per mesh device, replicated
    centroids, all-gather top-k merge.  Holds the shard-stacked array dict
    (``engine_sharded.shard_index`` layout), not a ``PlaidIndex``."""

    def __init__(
        self,
        idx_dict: dict,
        meta: dict,
        *,
        docs_per_shard: int,
        n_shards: int,
        params: SearchParams | None = None,
        mesh=None,
    ):
        self.params = params or SearchParams()
        self.mesh = mesh if mesh is not None else _default_mesh()
        n_devices = 1
        for v in self.mesh.shape.values():
            n_devices *= v
        if n_shards != n_devices:
            raise ValueError(
                f"n_shards={n_shards} must equal the mesh device count "
                f"({n_devices}); build the mesh to match the shard layout"
            )
        self._idx_dict = idx_dict
        self._meta = meta
        self.docs_per_shard = docs_per_shard
        self.n_shards = n_shards
        p = self.params
        self._engine_params = dataclasses.replace(
            to_engine_params(p),
            # stage-1 bound is per shard: clamp to the shard's corpus
            candidate_cap=min(p.candidate_cap, max(docs_per_shard, 2)),
        )
        # funnel flag -> compiled shard_map program; the funnel=True
        # variant is built lazily on the first with_funnel request (one
        # extra compile, never a retrace — funnel joins the cache key)
        self._search_fns = {False: self._make_search_fn(funnel=False)}

    def _make_search_fn(self, *, funnel: bool):
        return engine_sharded.make_sharded_search(
            self.mesh,
            self._engine_params,
            docs_per_shard=self.docs_per_shard,
            static_meta=self._meta,
            funnel=funnel,
        )

    @classmethod
    def build(cls, corpus_embs, cfg: RetrieverConfig, doc_lens=None):
        return cls.from_index(_build_index(corpus_embs, cfg, doc_lens), cfg)

    @classmethod
    def from_index(cls, index, cfg: RetrieverConfig):
        n_shards = cfg.n_shards or len(jax.devices())
        mesh = _default_mesh() if n_shards == len(jax.devices()) else None
        idx_dict, meta, per = engine_sharded.shard_index(
            index, n_shards, mesh=mesh
        )
        return cls(
            idx_dict,
            meta,
            docs_per_shard=per,
            n_shards=n_shards,
            params=cfg.params,
            mesh=mesh,
        )

    @classmethod
    def load(cls, path: str, params: SearchParams | None = None):
        import json
        import os

        idx_dict, meta, per = indexer.load_sharded(path)
        with open(os.path.join(path, "manifest.json")) as f:
            n_shards = json.load(f)["n_shards"]
        return cls(
            idx_dict, meta, docs_per_shard=per, n_shards=n_shards, params=params
        )

    def save(self, path: str) -> None:
        indexer.save_sharded_arrays(
            path,
            self._idx_dict,
            self._meta,
            n_shards=self.n_shards,
            docs_per_shard=self.docs_per_shard,
        )
        registry.write_meta(path, self)

    # ---- search ----------------------------------------------------------
    def _run(self, qs, q_masks, t_cs, funnel=False):
        if q_masks is None:
            q_masks = jnp.ones(qs.shape[:2], jnp.float32)
        if funnel not in self._search_fns:
            self._search_fns[funnel] = self._make_search_fn(funnel=funnel)
        return self._search_fns[funnel](self._idx_dict, qs, q_masks, t_cs)

    def search(self, q, q_mask=None, *, t_cs=None, with_diagnostics=False,
               with_funnel=False):
        req = _as_request(q, q_mask, t_cs, with_diagnostics, with_funnel)
        _reject_diagnostics(req, self.backend_name)
        t = self.params.t_cs if req.t_cs is None else req.t_cs
        mask = None if req.q_mask is None else req.q_mask[None]
        t0 = time.perf_counter()
        scores, pids, *aux = self._run(
            req.q[None], mask, t, funnel=req.with_funnel
        )
        out = (scores[0], pids[0])
        if req.with_funnel:
            fs = aux[0]
            out = (*out, type(fs)(*(v[0] for v in fs)))
        return _finish(
            out,
            backend=self.backend_name,
            k=self.params.k,
            t_cs=t,
            t0=t0,
            funnel=req.with_funnel,
        )

    def search_batch(self, qs, q_masks=None, *, t_cs=None,
                     with_diagnostics=False, with_funnel=False):
        req = _as_request(qs, q_masks, t_cs, with_diagnostics, with_funnel)
        _reject_diagnostics(req, self.backend_name)
        t = self.params.t_cs if req.t_cs is None else req.t_cs
        t0 = time.perf_counter()
        out = self._run(req.q, req.q_mask, t, funnel=req.with_funnel)
        return _finish(
            out, backend=self.backend_name, k=self.params.k, t_cs=t, t0=t0,
            funnel=req.with_funnel,
        )

    def describe(self) -> dict:
        return dict(
            backend=self.backend_name,
            static=self.params.static_dict(),
            dynamic=self.params.dynamic_dict(),
            static_fields=STATIC_FIELDS,
            dynamic_fields=DYNAMIC_FIELDS,
            sharding=dict(
                n_shards=self.n_shards,
                docs_per_shard=self.docs_per_shard,
                mesh=dict(self.mesh.shape),
                candidate_cap_per_shard=min(
                    self.params.candidate_cap, max(self.docs_per_shard, 2)
                ),
            ),
            index=dict(
                num_passages=self.n_shards * self.docs_per_shard,
                dim=self._meta["dim"],
                nbits=self._meta["nbits"],
                doc_maxlen=self._meta["doc_maxlen"],
            ),
            compile=dict(trace_count=plaid_mod.trace_count()),
        )


# --------------------------------------------------------------------------
# Tiered beyond-HBM PLAID
# --------------------------------------------------------------------------
@registry.register("plaid-tiered")
class TieredRetriever:
    """Beyond-HBM PLAID: device-resident funnel, host-resident payloads.

    Wraps :class:`repro.exec.tiered.TieredExecutor` (two-phase gather per
    partition, one shared top-k merge).  ``RetrieverConfig.n_shards`` sets
    the partition count (same knob the sharded backends use — here the
    partitions split the HOST tier, not a device mesh).  Results are
    bitwise rank-identical to ``"plaid"`` on the same index; what changes
    is residency: only finalists' CSR slices cross host->device per batch,
    accounted in ``transfer_totals`` / ``last_transfer``.
    """

    impl = "ref"

    def __init__(
        self,
        tiered,
        params: SearchParams | None = None,
        *,
        n_partitions: int = 1,
        device_budget_bytes: int | None = None,
    ):
        from repro.core import tiered as tiered_mod
        from repro.exec.tiered import TieredExecutor

        if not isinstance(tiered, tiered_mod.TieredIndex):
            tiered = tiered_mod.tiered_from_index(tiered)
        self.tiered = tiered
        self.params = params or SearchParams()
        self.n_partitions = max(int(n_partitions), 1)
        self._executor = TieredExecutor(
            tiered,
            to_engine_params(self.params, self.impl),
            n_partitions=self.n_partitions,
            device_budget_bytes=device_budget_bytes,
        )

    # ---- construction ----------------------------------------------------
    @classmethod
    def build(cls, corpus_embs, cfg: RetrieverConfig, doc_lens=None):
        return cls.from_index(_build_index(corpus_embs, cfg, doc_lens), cfg)

    @classmethod
    def from_index(cls, index, cfg: RetrieverConfig):
        return cls(index, cfg.params, n_partitions=cfg.n_shards or 1)

    @classmethod
    def load(cls, path: str, params: SearchParams | None = None):
        from repro.core import tiered as tiered_mod

        return cls(tiered_mod.load_tiered(path), params)

    def save(self, path: str) -> None:
        from repro.core import tiered as tiered_mod

        tiered_mod.save_tiered(path, self.tiered)
        registry.write_meta(path, self)

    # ---- transfer accounting (consumed by serving stats + benchmarks) ----
    @property
    def transfer_totals(self) -> dict:
        return self._executor.transfer_totals

    def last_transfer_bytes(self):
        return self._executor.last_transfer_bytes()

    # ---- search ----------------------------------------------------------
    def search(self, q, q_mask=None, *, t_cs=None, with_diagnostics=False,
               with_funnel=False):
        req = _as_request(q, q_mask, t_cs, with_diagnostics, with_funnel)
        _reject_diagnostics(req, self.backend_name)
        t = self.params.t_cs if req.t_cs is None else req.t_cs
        mask = None if req.q_mask is None else req.q_mask[None]
        t0 = time.perf_counter()
        scores, pids, *aux = self._executor.search_batch(
            req.q[None], mask, t, funnel=req.with_funnel
        )
        out = (scores[0], pids[0])
        if req.with_funnel:
            fs = aux[0]
            out = (*out, type(fs)(*(v[0] for v in fs)))
        return _finish(
            out,
            backend=self.backend_name,
            k=self.params.k,
            t_cs=t,
            t0=t0,
            funnel=req.with_funnel,
        )

    def search_batch(self, qs, q_masks=None, *, t_cs=None,
                     with_diagnostics=False, with_funnel=False):
        req = _as_request(qs, q_masks, t_cs, with_diagnostics, with_funnel)
        _reject_diagnostics(req, self.backend_name)
        t = self.params.t_cs if req.t_cs is None else req.t_cs
        t0 = time.perf_counter()
        out = self._executor.search_batch(
            req.q, req.q_mask, t, funnel=req.with_funnel
        )
        return _finish(
            out, backend=self.backend_name, k=self.params.k, t_cs=t, t0=t0,
            funnel=req.with_funnel,
        )

    # ---- introspection ---------------------------------------------------
    def describe(self) -> dict:
        from repro.core import tiered as tiered_mod

        t = self.tiered
        traces_a, traces_b = tiered_mod.trace_counts()
        return dict(
            backend=self.backend_name,
            impl=self.impl,
            static=self.params.static_dict(),
            dynamic=self.params.dynamic_dict(),
            static_fields=STATIC_FIELDS,
            dynamic_fields=DYNAMIC_FIELDS,
            storage=dict(
                mode="tiered",
                n_partitions=self.n_partitions,
                device_bytes=self._executor.device_nbytes(),
                resident_payload_bytes=(
                    self._executor.resident_payload_nbytes()
                ),
                device_budget_bytes=self._executor.device_budget_bytes,
                payload_itemsize=t.payload_itemsize,
            ),
            transfer=self.transfer_totals,
            index=dict(
                num_passages=t.num_passages,
                num_tokens=t.num_tokens,
                num_centroids=t.device.num_centroids,
                dim=t.device.dim,
                nbits=t.device.nbits,
                doc_maxlen=t.device.doc_maxlen,
            ),
            compile=dict(
                phase_a_traces=traces_a, phase_b_traces=traces_b
            ),
        )


@registry.register("plaid-tiered-pallas")
class TieredPallasRetriever(TieredRetriever):
    """Tiered PLAID through the Pallas kernels — the fused tail's CSR
    window gather runs over the compacted slice arrays."""

    impl = "pallas"
