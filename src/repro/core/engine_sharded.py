"""Document-sharded PLAID engine: host-side index partitioning + adapter.

The corpus is partitioned into ``n_shards`` equal sub-corpora, one per mesh
device (all three axes pod x data x model are used as one flat "docs" axis —
retrieval is embarrassingly parallel over documents).  Centroids are
replicated (they are K x 128, small).

Execution lives in the partition-execution layer: :mod:`repro.exec.sharded`
runs the full 4-stage pipeline per shard under ``shard_map`` and joins the
one shared merge in ``repro.distributed.topk`` — this module holds NO merge
logic of its own.  What stays here is the *host-side* partitioner
:func:`shard_index` (build one global index, split by document range) plus
compatibility re-exports.

Fault tolerance: a shard's index is a pure pytree of arrays — a respawned
host reloads its shard from the index store and rejoins; no cross-shard
state exists.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

# Compatibility re-exports: the version shim lives in repro.compat, the
# execution primitives in repro.exec.sharded.  Import from those homes in
# new code.
from repro.compat import shard_map  # noqa: F401
from repro.core.index import PlaidIndex
from repro.exec.sharded import (  # noqa: F401
    DOC_AXES,
    doc_axes as _doc_axes,
    index_as_dict as _index_as_dict,
    index_shardings,
    index_spec_tree as _index_spec_tree,
    make_sharded_search,
)


def static_meta_of(index: PlaidIndex) -> dict:
    import dataclasses as _dc

    return {
        f.name: getattr(index, f.name)
        for f in _dc.fields(PlaidIndex)
        if f.metadata.get("static")
    }


def shard_index(index: PlaidIndex, n_shards: int, mesh=None):
    """Partition a globally-built index into equal doc-range shards.

    The deployment path: build ONE index (shared centroid space), split by
    document range, stack shard arrays along axis 0 for the sharded engine.
    Per-shard IVFs are recomputed over the shared centroids with LOCAL pids.
    Returns (index_dict, static_meta, docs_per_shard) ready for
    ``make_sharded_search``.  With a ``mesh`` the stacked arrays are placed
    by ``index_shardings`` straight from the host, so each device holds
    only its shard; without one they land on the default device.

    Shard ``i`` owns global pids ``[i * per, min((i + 1) * per, Nd))``, so
    a sharded pid (``shard * per + local``) IS the original global pid —
    padded tail slots (zero doc length, absent from every IVF) can never
    surface as candidates.  ``repro.exec.live`` relies on this to shard a
    LiveIndex base segment without remapping its pid space.
    """
    import numpy as np

    Nd = index.num_passages
    per = -(-Nd // n_shards)  # ceil
    K = index.num_centroids
    doc_off = np.asarray(index.doc_offsets)
    doc_lens = np.asarray(index.doc_lens)
    codes = np.asarray(index.codes)
    residuals = np.asarray(index.residuals)

    sh = {k: [] for k in (
        "codes", "residuals", "tok_pid", "doc_offsets", "doc_lens",
        "ivf_pids", "ivf_offsets", "ivf_lens",
        "eivf_eids", "eivf_offsets", "eivf_lens",
    )}
    max_nt = max_nnz = 1
    for i in range(n_shards):
        lo, hi = i * per, min((i + 1) * per, Nd)
        t0, t1 = int(doc_off[lo]), int(doc_off[hi])
        lens = np.zeros(per, np.int32)
        lens[: hi - lo] = doc_lens[lo:hi]
        offs = np.zeros(per + 1, np.int32)
        np.cumsum(lens, out=offs[1:])
        c = codes[t0:t1]
        tok_pid = np.repeat(np.arange(per, dtype=np.int32), lens)
        pairs = np.unique(np.stack([c.astype(np.int64), tok_pid.astype(np.int64)], 1), axis=0) if len(c) else np.zeros((0, 2), np.int64)
        ivf_lens = np.bincount(pairs[:, 0], minlength=K).astype(np.int32)
        ivf_offsets = np.zeros(K + 1, np.int32)
        np.cumsum(ivf_lens, out=ivf_offsets[1:])
        eivf = np.argsort(c, kind="stable").astype(np.int32)
        eivf_lens = np.bincount(c, minlength=K).astype(np.int32)
        eivf_offsets = np.zeros(K + 1, np.int32)
        np.cumsum(eivf_lens, out=eivf_offsets[1:])
        sh["codes"].append(c)
        sh["residuals"].append(residuals[t0:t1])
        sh["tok_pid"].append(tok_pid)
        sh["doc_offsets"].append(offs)
        sh["doc_lens"].append(lens)
        sh["ivf_pids"].append(pairs[:, 1].astype(np.int32))
        sh["ivf_offsets"].append(ivf_offsets)
        sh["ivf_lens"].append(ivf_lens)
        sh["eivf_eids"].append(eivf)
        sh["eivf_offsets"].append(eivf_offsets)
        sh["eivf_lens"].append(eivf_lens)
        max_nt = max(max_nt, t1 - t0)
        max_nnz = max(max_nnz, len(pairs))

    def pad(a, n):
        return np.pad(a, [(0, n - a.shape[0])] + [(0, 0)] * (a.ndim - 1))

    out = {
        "centroids": index.centroids,
        "centroids_q": index.centroids_q,
        "centroids_scale": index.centroids_scale,
        "cutoffs": index.cutoffs,
        "weights": index.weights,
    }
    for k, per_len in (
        ("codes", max_nt), ("residuals", max_nt), ("tok_pid", max_nt),
        ("ivf_pids", max_nnz), ("eivf_eids", max_nt),
    ):
        out[k] = np.concatenate([pad(a, per_len) for a in sh[k]])
    for k in ("doc_offsets", "doc_lens", "ivf_offsets", "ivf_lens",
              "eivf_offsets", "eivf_lens"):
        out[k] = np.concatenate(sh[k])
    if mesh is None:
        out = {k: jnp.asarray(v) for k, v in out.items()}
    else:
        out = jax.device_put(out, index_shardings(mesh, out))

    ivf_cap = int(max(ls.max(initial=1) for ls in sh["ivf_lens"]))
    eivf_cap = int(max(ls.max(initial=1) for ls in sh["eivf_lens"]))
    meta = dict(
        dim=index.dim,
        nbits=index.nbits,
        doc_maxlen=index.doc_maxlen,
        ivf_list_cap=ivf_cap,
        eivf_list_cap=eivf_cap,
        prune_fraction=index.prune_fraction,
    )
    return out, meta, per
