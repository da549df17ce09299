"""Pure-jnp oracles for every kernel in this package.

These delegate to the engine's reference scoring/codec so the kernels are
validated against the exact math the engine uses in ``impl="ref"`` mode.
The ``NEG`` sentinel is imported from ``repro.constants`` — the ONE place
it is defined — so fused/unfused/ref tie-breaking stays bitwise-comparable
(a locally-redefined sentinel would silently reorder equal-score ties;
pinned in ``tests/test_pipeline.py``).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.constants import NEG
from repro.core import residual_codec as rc
from repro.core import scoring


def centroid_interaction_ref(s_cq, codes, keep, q_mask):
    return scoring.centroid_interaction(
        s_cq, codes, q_mask=q_mask, keep_centroid=keep
    )


def decompress_residuals_ref(packed, weights, *, nbits: int):
    idx = rc.unpack_indices(packed, nbits)
    return weights.astype(jnp.float32)[idx]


def decompress_and_score_ref(
    q, q_mask, codes, packed_res, tok_valid, centroids, weights, *, nbits: int
):
    safe = jnp.where(codes >= 0, codes, 0)
    resid = decompress_residuals_ref(packed_res, weights, nbits=nbits)
    emb = centroids.astype(jnp.float32)[safe] + resid
    return scoring.maxsim(q, emb, q_mask=q_mask, d_mask=tok_valid)


def gather_decompress_maxsim_ref(
    qs: jax.Array,  # (B, nq, d)
    q_masks: jax.Array,  # (B, nq)
    final_pids: jax.Array,  # (B, n3) i32, -1 pad
    codes_tok: jax.Array,  # (Nt,) i32
    residuals_tok: jax.Array,  # (Nt, pd) u8
    doc_offsets: jax.Array,  # (Nd+1,)
    doc_lens: jax.Array,  # (Nd,)
    centroids: jax.Array,  # (K, d)
    weights: jax.Array,  # (2^b,)
    *,
    nbits: int,
    doc_maxlen: int,
) -> jax.Array:
    """Reference path for the pid-addressed stage-3-5 tail
    (``fused_score.gather_decompress_maxsim_pallas``): gather the finalist
    passages' codes + packed residuals straight from the CSR token arrays,
    decompress, and MaxSim — same op order as the unfused
    ``pipeline.decompress_score_batched``, so for valid pids the two are
    bitwise identical (pid == -1 lanes are pinned by the caller's final
    ``where`` in both paths)."""
    B, n3 = final_pids.shape
    flat_pids = final_pids.reshape(-1)
    codes_blk, tok_valid = scoring.gather_doc_tokens(
        codes_tok, doc_offsets, doc_lens, flat_pids, doc_maxlen, fill=-1
    )
    res_blk, _ = scoring.gather_doc_tokens(
        residuals_tok, doc_offsets, doc_lens, flat_pids, doc_maxlen,
        fill=jnp.uint8(0),
    )
    codes_blk = codes_blk.reshape(B, n3, doc_maxlen)
    tok_valid = tok_valid.reshape(B, n3, doc_maxlen)
    res_blk = res_blk.reshape(B, n3, doc_maxlen, -1)
    safe = jnp.where(codes_blk >= 0, codes_blk, 0)
    resid = decompress_residuals_ref(res_blk, weights, nbits=nbits)
    emb = centroids.astype(jnp.float32)[safe] + resid
    scores = jnp.einsum(  # (B, n3, nq, L) — f32 on every backend
        "bqd,bntd->bnqt", qs, emb, precision=jax.lax.Precision.HIGHEST
    )
    scores = jnp.where(tok_valid[:, :, None, :], scores, NEG)
    per_q = scores.max(axis=-1)  # (B, n3, nq)
    per_q = per_q * q_masks[:, None, :]
    return per_q.sum(axis=-1)
