"""Pallas TPU kernel: centroid interaction over candidate passages (§4.5).

The paper's C++ kernel loops over each passage's packed token vectors and
keeps an O(|Q|) running-max accumulator per passage.  On the TPU the
per-token row gather ``S_cq[code]`` cannot run inside a Mosaic kernel (its
gather rule takes only a 2-D ``take_along_axis`` of identical shapes), so
the gather is hoisted into XLA — the same split
``pipeline.gather_candidate_tokens_shared`` makes for the codes — and the
kernel does the reduction: grid ``(B, doc_blocks)``, each step a
``(BD, nq, L)`` tile of gathered scores reduced max-over-tokens, then
relu, query mask and sum-over-query-tokens, writing only ``(BD,)`` scores.

Layout: tokens on the lane axis (``L = doc_maxlen`` is 128 at cell widths)
and query tokens on sublanes, so neither the tile nor its HBM copy pads
``nq = 32`` out to 128 lanes.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.constants import NEG
from repro.kernels.dispatch import resolve_interpret


def gather_token_scores(s_cq, codes, keep):
    """(B, K, nq) scores x (B, nd, L) codes -> (B, nd, nq, L) f32 token
    scores, ``NEG`` where the token is padding or its centroid is pruned."""
    B, nd, L = codes.shape
    valid = codes >= 0
    safe = jnp.where(valid, codes, 0).reshape(B, nd * L)
    tok = jnp.take_along_axis(s_cq, safe[..., None], axis=1)  # (B, nd*L, nq)
    kept = jnp.take_along_axis(keep, safe, axis=1).reshape(B, nd, L)
    tok = jnp.where(
        (valid & kept)[..., None], tok.reshape(B, nd, L, -1).astype(jnp.float32),
        NEG,
    )
    return tok.transpose(0, 1, 3, 2)


def _centroid_interaction_kernel(
    tok_ref,  # (1, BD, nq, L) f32 gathered token scores
    q_mask_ref,  # (1, 1, nq) f32
    out_ref,  # (1, BD, 1) f32
):
    per_q = jnp.maximum(tok_ref[0].max(axis=-1), 0.0)  # (BD, nq)
    out_ref[0] = (per_q * q_mask_ref[0]).sum(axis=-1, keepdims=True)


def centroid_interaction_batched_pallas(
    s_cq: jax.Array,  # (B, K, nq)
    codes: jax.Array,  # (B, nd, L) i32, -1 padding
    keep: jax.Array,  # (B, K) bool
    q_mask: jax.Array,  # (B, nq)
    *,
    doc_block: int = 32,
    interpret: bool | None = None,
) -> jax.Array:
    """Batch-first stage-2/3 interaction: (B, nd) approximate scores from
    one kernel launch with grid (B, doc_blocks)."""
    interpret = resolve_interpret(interpret)
    B, nd, L = codes.shape
    nq = s_cq.shape[2]
    pad = (-nd) % doc_block
    if pad:
        codes = jnp.pad(codes, ((0, 0), (0, pad), (0, 0)), constant_values=-1)
    tok = gather_token_scores(s_cq, codes, keep)
    out = pl.pallas_call(
        _centroid_interaction_kernel,
        grid=(B, (nd + pad) // doc_block),
        in_specs=[
            pl.BlockSpec((1, doc_block, nq, L), lambda b, i: (b, i, 0, 0)),
            pl.BlockSpec((1, 1, nq), lambda b, i: (b, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, doc_block, 1), lambda b, i: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, nd + pad, 1), jnp.float32),
        interpret=interpret,
    )(tok, q_mask.astype(jnp.float32)[:, None, :])
    return out[:, :nd, 0]
