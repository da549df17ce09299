"""Stage-3-5 tail straight off the CSR token arrays: gather -> decompress ->
MaxSim for the finalist passages, addressed by pid.

The unfused tail consumes the codes/validity blocks stage 2 already
gathered and gathers only the residuals; this entry point gathers each
finalist's codes AND packed residuals directly from the index's CSR arrays
and scores them with the stage-4 kernel
(``decompress.decompress_and_score_batched_pallas``).

The windows are gathered by XLA.  Reading them inside the kernel through
``pl.Element`` row offsets does not lower for the TPU: Mosaic requires a
window's first row to be a multiple of the 8-row tiling, CSR windows start
at any token, and no aligned window can reach the last rows of a token
array whose length is not a multiple of 8.
"""
from __future__ import annotations

import jax

from repro.core import scoring
from repro.kernels.decompress import decompress_and_score_batched_pallas


def gather_decompress_maxsim_pallas(
    qs: jax.Array,  # (B, nq, d)
    q_masks: jax.Array,  # (B, nq)
    final_pids: jax.Array,  # (B, n3) i32, -1 pad
    codes_tok: jax.Array,  # (Nt,) i32 — the index's packed token codes
    residuals_tok: jax.Array,  # (Nt, pd) u8 — packed residual bytes
    doc_offsets: jax.Array,  # (Nd+1,) i32
    doc_lens: jax.Array,  # (Nd,) i32
    centroids: jax.Array,  # (K, d)
    weights: jax.Array,  # (2^b,)
    *,
    nbits: int,
    doc_maxlen: int,
    interpret: bool | None = None,
) -> jax.Array:
    """Exact MaxSim scores (B, n3) for the finalist passages.  Scores for
    ``pid == -1`` lanes are ``nq * NEG``-ish values the caller overrides;
    every valid lane matches ``decompress_and_score_batched``."""
    B, n3 = final_pids.shape
    flat = final_pids.reshape(-1)
    codes, valid = scoring.gather_doc_tokens(
        codes_tok, doc_offsets, doc_lens, flat, doc_maxlen, fill=-1
    )
    res, _ = scoring.gather_doc_tokens(
        residuals_tok, doc_offsets, doc_lens, flat, doc_maxlen, fill=0
    )
    return decompress_and_score_batched_pallas(
        qs,
        q_masks,
        codes.reshape(B, n3, doc_maxlen),
        res.reshape(B, n3, doc_maxlen, -1),
        valid.reshape(B, n3, doc_maxlen),
        centroids,
        weights,
        nbits=nbits,
        interpret=interpret,
    )
