"""Jit'd public wrappers around the Pallas kernels (+ engine adapters).

The engine (``repro.core.pipeline`` / ``repro.core.plaid``) calls these when
``SearchParams.impl == "pallas"``.  Execution mode is platform-aware:
``interpret=None`` (the default) resolves via ``jax.default_backend()`` —
the Pallas interpreter off-TPU, the Mosaic lowering on TPU
(``repro.kernels.dispatch``).  Pass an explicit bool to override per call.

The ``*_batched`` wrappers take a leading batch axis and launch ONE kernel
with a ``(B, doc_blocks)`` grid, so resident tiles (centroids, codec
weights, per-lane S_cq / query tiles) are amortized across the batch
instead of being re-fetched by a per-lane ``vmap``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import decompress as _dec
from repro.kernels import fused_score as _fs
from repro.kernels import maxsim as _ms
from repro.kernels.dispatch import default_interpret, resolve_interpret

__all__ = [
    "centroid_interaction",
    "centroid_interaction_batched",
    "decompress_residuals",
    "decompress_and_score",
    "decompress_and_score_batched",
    "gather_decompress_maxsim",
    "default_interpret",
]


@functools.partial(jax.jit, static_argnames=("interpret", "doc_block"))
def centroid_interaction(
    s_cq: jax.Array,
    codes: jax.Array,
    q_mask: jax.Array | None = None,
    keep_centroid: jax.Array | None = None,
    *,
    interpret: bool | None = None,
    doc_block: int = 32,
) -> jax.Array:
    """Engine-compatible signature (matches ``scoring.centroid_interaction``):
    the batched kernel over a batch of one."""
    if q_mask is None:
        q_mask = jnp.ones(s_cq.shape[1], jnp.float32)
    if keep_centroid is None:
        keep_centroid = jnp.ones(s_cq.shape[0], bool)
    return _ms.centroid_interaction_batched_pallas(
        s_cq[None],
        codes[None],
        keep_centroid[None],
        q_mask[None],
        doc_block=doc_block,
        interpret=resolve_interpret(interpret),
    )[0]


@functools.partial(jax.jit, static_argnames=("interpret", "doc_block"))
def centroid_interaction_batched(
    s_cq: jax.Array,  # (B, K, nq)
    codes: jax.Array,  # (B, nd, L)
    q_mask: jax.Array | None = None,  # (B, nq)
    keep_centroid: jax.Array | None = None,  # (B, K)
    *,
    interpret: bool | None = None,
    doc_block: int = 32,
) -> jax.Array:
    """Batch-first stage-2/3 interaction (grid (B, doc_blocks))."""
    if q_mask is None:
        q_mask = jnp.ones((s_cq.shape[0], s_cq.shape[2]), jnp.float32)
    if keep_centroid is None:
        keep_centroid = jnp.ones(s_cq.shape[:2], bool)
    return _ms.centroid_interaction_batched_pallas(
        s_cq,
        codes,
        keep_centroid,
        q_mask,
        doc_block=doc_block,
        interpret=resolve_interpret(interpret),
    )


@functools.partial(jax.jit, static_argnames=("nbits", "interpret", "row_block"))
def decompress_residuals(
    packed: jax.Array,
    weights: jax.Array,
    *,
    nbits: int,
    interpret: bool | None = None,
    row_block: int = 256,
) -> jax.Array:
    lead = packed.shape[:-1]
    flat = packed.reshape(-1, packed.shape[-1])
    out = _dec.decompress_residuals_pallas(
        flat,
        weights,
        nbits=nbits,
        row_block=row_block,
        interpret=resolve_interpret(interpret),
    )
    return out.reshape(*lead, out.shape[-1])


@functools.partial(jax.jit, static_argnames=("nbits", "interpret", "doc_block"))
def decompress_and_score(
    q: jax.Array,
    q_mask: jax.Array,
    codes: jax.Array,
    packed_res: jax.Array,
    tok_valid: jax.Array,
    centroids: jax.Array,
    weights: jax.Array,
    *,
    nbits: int,
    interpret: bool | None = None,
    doc_block: int = 8,
) -> jax.Array:
    """Single-query stage 4: the batched kernel over a batch of one."""
    return _dec.decompress_and_score_batched_pallas(
        q[None],
        q_mask[None],
        codes[None],
        packed_res[None],
        tok_valid[None],
        centroids,
        weights,
        nbits=nbits,
        doc_block=doc_block,
        interpret=resolve_interpret(interpret),
    )[0]


@functools.partial(jax.jit, static_argnames=("nbits", "interpret", "doc_block"))
def decompress_and_score_batched(
    q: jax.Array,  # (B, nq, d)
    q_mask: jax.Array,  # (B, nq)
    codes: jax.Array,  # (B, nd, L)
    packed_res: jax.Array,  # (B, nd, L, pd)
    tok_valid: jax.Array,  # (B, nd, L)
    centroids: jax.Array,  # (K, d)
    weights: jax.Array,  # (2^b,)
    *,
    nbits: int,
    interpret: bool | None = None,
    doc_block: int = 8,
) -> jax.Array:
    """Batch-first fused stage-4 kernel (grid (B, doc_blocks))."""
    return _dec.decompress_and_score_batched_pallas(
        q,
        q_mask,
        codes,
        packed_res,
        tok_valid,
        centroids,
        weights,
        nbits=nbits,
        doc_block=doc_block,
        interpret=resolve_interpret(interpret),
    )


@functools.partial(
    jax.jit, static_argnames=("nbits", "doc_maxlen", "interpret")
)
def gather_decompress_maxsim(
    qs: jax.Array,  # (B, nq, d)
    q_masks: jax.Array,  # (B, nq)
    final_pids: jax.Array,  # (B, n3) i32, -1 pad
    codes_tok: jax.Array,  # (Nt,) i32 — CSR token codes, NOT pre-gathered
    residuals_tok: jax.Array,  # (Nt, pd) u8 — CSR packed residuals
    doc_offsets: jax.Array,  # (Nd+1,)
    doc_lens: jax.Array,  # (Nd,)
    centroids: jax.Array,  # (K, d)
    weights: jax.Array,  # (2^b,)
    *,
    nbits: int,
    doc_maxlen: int,
    interpret: bool | None = None,
) -> jax.Array:
    """The stage-3-5 tail addressed by pid: the finalists' CSR windows are
    gathered and scored by the stage-4 kernel.  Returns (B, n3) exact
    scores (pid == -1 lanes are the caller's to pin).
    """
    return _fs.gather_decompress_maxsim_pallas(
        qs,
        q_masks,
        final_pids,
        codes_tok,
        residuals_tok,
        doc_offsets,
        doc_lens,
        centroids,
        weights,
        nbits=nbits,
        doc_maxlen=doc_maxlen,
        interpret=resolve_interpret(interpret),
    )
