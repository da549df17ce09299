"""Device-sharded partition execution: one mesh device = one doc partition.

The shard_map half of the partition-execution layer (``repro.exec``): every
device runs the stock batch-first pipeline (``core.pipeline``) on its
sub-corpus, offsets local pids into the global id space, and joins the one
shared merge (``distributed.topk.merge_topk`` over the mesh axis — the
collective case; gathered bytes are independent of corpus size).

The tombstone ``alive`` bitmap is a TRACED operand, doc-partitioned like
the corpus arrays, so a sharded index can serve a mutable pid space
(``repro.exec.live``): deletes never recompile and never touch the shards.

``repro.core.engine_sharded`` is a thin adapter over this module (it keeps
the host-side index partitioner ``shard_index`` and the public
``make_sharded_search`` name); the merge itself lives only in
``distributed.topk``.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.compat import shard_map
from repro.core import pipeline
from repro.core.index import PlaidIndex
from repro.distributed import topk as dtopk
from repro.obs import funnel as funnel_mod

DOC_AXES = ("pod", "data", "model")  # flattened into one logical docs axis

_REPLICATED_FIELDS = {
    "centroids", "centroids_q", "centroids_scale", "cutoffs", "weights"
}

#: Fallback static metadata for dry-run callers that pass bare array dicts.
_DEFAULT_META = dict(
    dim=128, nbits=2, doc_maxlen=128, ivf_list_cap=256, eivf_list_cap=512
)


def doc_axes(mesh):
    return tuple(a for a in DOC_AXES if a in mesh.axis_names)


def n_doc_shards(mesh) -> int:
    n = 1
    for a in doc_axes(mesh):
        n *= mesh.shape[a]
    return n


def index_spec_tree(doc, rep):
    """Field-name -> PartitionSpec dict matching PlaidIndex's array fields
    (dicts avoid treedef mismatches from PlaidIndex's static metadata)."""
    specs = {}
    for f in dataclasses.fields(PlaidIndex):
        if f.metadata.get("static"):
            continue
        specs[f.name] = rep if f.name in _REPLICATED_FIELDS else doc
    return specs


def index_as_dict(index: PlaidIndex):
    return {
        f.name: getattr(index, f.name)
        for f in dataclasses.fields(PlaidIndex)
        if not f.metadata.get("static")
    }


def index_shardings(mesh, fields) -> dict:
    """Field name -> ``NamedSharding`` for a shard-stacked index dict.

    Doc-partitioned arrays shard their leading axis over the mesh's doc
    axes, so each device holds only its own shard; centroid-space arrays
    (centroids, codec tables) replicate.
    """
    doc = NamedSharding(mesh, P(doc_axes(mesh)))
    rep = NamedSharding(mesh, P())
    return {
        name: (rep if name in _REPLICATED_FIELDS else doc) for name in fields
    }


def make_sharded_search(
    mesh,
    params,  # plaid.SearchParams
    *,
    docs_per_shard: int,
    static_meta: dict | None = None,
    interpret: bool | None = None,
    funnel: bool = False,
):
    """Returns jit-able ``search(index, qs, q_masks, t_cs, alive) -> (scores, pids)``.

    ``funnel=True`` appends a mesh-merged ``obs.FunnelStats`` output:
    doc-space counts ``psum`` over the mesh axis, centroid-space counts
    (identical on every shard — centroids replicate) pass through.

    ``index`` holds the shard-stacked arrays (``shard_index`` layout): every
    doc-partitioned array has a leading global axis = n_shards * per-shard
    size, sharded over the full mesh; per-shard offset arrays are LOCAL
    (each shard's doc_offsets index into its own codes/residuals).  Queries
    are replicated to all shards.

    ``t_cs`` and ``alive`` are traced: threshold sweeps and tombstone flips
    reuse the compiled program.  ``alive`` is a ``(n_shards *
    docs_per_shard,)`` bool bitmap in the sharded (padded) pid space;
    ``None`` compiles an all-alive constant.
    """
    ax = doc_axes(mesh)
    doc = P(ax)
    rep = P()
    index_specs = index_spec_tree(doc, rep)

    # NOT clamped to candidate_cap here: the pipeline clamps stage-2's keep
    # (n2) itself but derives stage-3's keep from the raw ndocs//4 — pre-
    # clamping would silently shrink stage 3.
    meta = dict(_DEFAULT_META)
    meta.update(static_meta or {})

    def local_search(index_dict, qs, q_masks, t_cs, alive):
        axis = ax[0] if len(ax) == 1 else ax
        index_local = PlaidIndex(**index_dict, **meta)
        # The batch-first pipeline per shard: one C.Q^T matmul and one
        # shared candidate-token gather for the whole query batch (§Perf
        # S1) — the shard's centroid matrix streams from HBM once.
        out = pipeline.run_pipeline_impl(
            index_local, qs, q_masks, t_cs, params=params, alive=alive,
            interpret=interpret, funnel=funnel,
        )  # (B, k) per shard
        scores, pids, *aux = out
        pids = dtopk.local_to_global_pids(pids, axis, docs_per_shard)
        # the one shared merge, batched over B (gathers (B, k) tuples only)
        merged = dtopk.merge_topk(scores, pids, params.k, axis_name=axis)
        if funnel:
            return (*merged, funnel_mod.psum_partitions(aux[0], axis))
        return merged

    out_specs = (rep, rep, rep) if funnel else (rep, rep)
    search = shard_map(
        local_search,
        mesh=mesh,
        in_specs=(index_specs, rep, rep, rep, doc),
        out_specs=out_specs,
        check_rep=False,
    )
    n_total = n_doc_shards(mesh) * docs_per_shard

    def run(index, qs, q_masks, t_cs=None, alive=None):
        """index: PlaidIndex or a dict of its array fields (dry-run SDS).

        ``t_cs``/``alive`` are traced (replicated / doc-partitioned):
        sweeping the threshold or flipping tombstones at serve time reuses
        the compiled program; ``None`` means ``params.t_cs`` / all-alive.
        """
        if isinstance(index, PlaidIndex):
            index = index_as_dict(index)
        t = jnp.float32(params.t_cs if t_cs is None else t_cs)
        if alive is None:  # resolved at trace time: baked-in constant
            alive = jnp.ones((n_total,), bool)
        return search(index, qs, q_masks, t, alive)

    return jax.jit(run)
