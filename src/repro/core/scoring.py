"""Reference (pure-jnp) scoring ops shared by the engine and the kernels.

These are the oracles the Pallas kernels in ``repro.kernels`` are validated
against, and the default execution path on CPU.  All shapes are static; the
``-1`` sentinel marks padded candidate slots / padded tokens.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.constants import NEG  # sentinel score for pruned / invalid entries


def maxsim(q: jax.Array, d: jax.Array, q_mask=None, d_mask=None) -> jax.Array:
    """Exact late-interaction score, Eq. 1:  sum_i max_j  Q_i . D_j.

    q: (nq, dim); d: (nd, ldoc, dim); masks broadcastable to (nq,)/(nd, ldoc).
    Returns (nd,) scores.
    """
    scores = jnp.einsum("qd,ntd->nqt", q, d)  # (nd, nq, ldoc)
    if d_mask is not None:
        scores = jnp.where(d_mask[:, None, :], scores, NEG)
    per_q = scores.max(axis=-1)  # (nd, nq)
    if q_mask is not None:
        per_q = per_q * q_mask[None, :]
    return per_q.sum(axis=-1)


def centroid_scores(
    q: jax.Array,
    centroids: jax.Array,
    dtype=jnp.float32,
    *,
    operand_dtype: str = "float32",
    centroids_q: jax.Array | None = None,
    centroids_scale: jax.Array | None = None,
) -> jax.Array:
    """Stage-1 score matrix  S_cq = C . Q^T, returned as (K, nq).

    ``dtype=bfloat16`` (§Perf S2) halves the footprint of the score matrix
    and of every stage-2/3 gather from it; stages 1-3 only SELECT candidates
    (exact ranking happens in stage 4), so bf16 noise (~1e-2 relative on
    cosine scores) does not measurably change recall (tested).

    ``operand_dtype`` is the single-query mirror of the batched pipeline's
    ``SearchParams.stage1_dtype`` — it lowers the *matmul operand*
    precision (centroid-table read traffic), keeping f32 accumulation:
    ``"bfloat16"`` casts both operands; ``"int8"`` streams the index's
    weight-only-quantized table (pass ``centroids_q``/``centroids_scale``,
    see ``index.quantize_centroids``) and rescales after the dot.
    """
    if operand_dtype == "float32":
        out = centroids.astype(jnp.float32) @ q.astype(jnp.float32).T
    elif operand_dtype == "bfloat16":
        out = jax.lax.dot(
            centroids.astype(jnp.bfloat16),
            q.astype(jnp.bfloat16).T,
            preferred_element_type=jnp.float32,
        )
    elif operand_dtype == "int8":
        if centroids_q is None or centroids_scale is None:
            raise ValueError(
                "operand_dtype='int8' needs centroids_q/centroids_scale "
                "(index.quantize_centroids tables)"
            )
        out = (
            centroids_q.astype(jnp.float32) @ q.astype(jnp.float32).T
        ) * centroids_scale[:, None]
    else:
        raise ValueError(f"unknown operand_dtype: {operand_dtype!r}")
    return out.astype(dtype)


def centroid_interaction(
    s_cq: jax.Array,  # (K, nq) query-centroid scores
    codes: jax.Array,  # (nd, ldoc) i32 centroid id per candidate token (-1 pad)
    q_mask: jax.Array | None = None,  # (nq,)
    keep_centroid: jax.Array | None = None,  # (K,) bool — centroid pruning
) -> jax.Array:
    """Approximate MaxSim with centroids as token proxies (paper Eq. 3-4).

    With ``keep_centroid`` given, tokens assigned to pruned centroids are
    skipped (paper Eq. 5) — this is *centroid pruning* (stage 2); without it
    this is full centroid interaction (stage 3).
    Returns (nd,) approximate scores.
    """
    valid = codes >= 0
    safe = jnp.where(valid, codes, 0)
    tok_scores = s_cq[safe]  # (nd, ldoc, nq) gather of score rows
    if keep_centroid is not None:
        valid = valid & keep_centroid[safe]
    tok_scores = jnp.where(
        valid[..., None], tok_scores, jnp.asarray(NEG, tok_scores.dtype)
    )
    per_q = tok_scores.max(axis=1).astype(jnp.float32)  # (nd, nq)
    per_q = jnp.maximum(per_q, 0.0)  # empty/pruned docs floor at 0, not nq*NEG
    if q_mask is not None:
        per_q = per_q * q_mask[None, :]
    return per_q.sum(axis=-1)


def prune_mask(s_cq: jax.Array, t_cs: float) -> jax.Array:
    """(K,) bool: centroid survives iff its best query-token score >= t_cs."""
    return s_cq.max(axis=-1) >= t_cs


def gather_doc_tokens(
    values: jax.Array,  # (Nt, ...) packed per-token payload
    doc_offsets: jax.Array,  # (Nd+1,)
    doc_lens: jax.Array,  # (Nd,)
    pids: jax.Array,  # (nd,) candidate ids, -1 = pad
    doc_maxlen: int,
    fill,
) -> jax.Array:
    """Gather packed per-token payload into a (nd, doc_maxlen, ...) block.

    A 1-D payload (the codes) is gathered a window at a time
    (``_gather_windows``); a payload with per-token rows (the residuals)
    gathers one row per token.  Slots past a passage's length, and every
    slot of a ``-1`` pad, are overwritten with ``fill``.
    """
    safe_pid = jnp.where(pids >= 0, pids, 0)
    start = doc_offsets[safe_pid]  # (nd,)
    lens = jnp.where(pids >= 0, doc_lens[safe_pid], 0)
    pos = jnp.arange(doc_maxlen, dtype=jnp.int32)
    valid = pos[None, :] < lens[:, None]
    if values.ndim == 1:
        out = _gather_windows(values, start, doc_maxlen)
    else:
        out = values[jnp.where(valid, start[:, None] + pos[None, :], 0)]
    mask_shape = valid.shape + (1,) * (out.ndim - 2)
    return jnp.where(valid.reshape(mask_shape), out, fill), valid


def _gather_windows(values: jax.Array, start: jax.Array, n: int) -> jax.Array:
    """(nd, n) block of ``values[start[i] : start[i] + n]`` for a 1-D payload.

    A TPU gather costs per index, not per byte, so each window is fetched
    as two aligned rows of W = 128 * ceil(n / 128) slots, which always cover
    it: ``values`` is viewed as rows of W (its tail padded), one gather with
    slice size (1, W) takes rows ``start // W`` and ``start // W + 1`` of
    every window, and a barrel shift of log2(W) static-slice selects moves
    each 2W-slot pair left by ``start % W``.  Slots past the end of
    ``values`` read the padding.
    """
    W = 128 * -(-n // 128)
    nt = values.shape[0]
    rows = jnp.pad(values, (0, (nt // W + 2) * W - nt)).reshape(-1, W)
    r = start // W
    x = rows[jnp.stack([r, r + 1], axis=1)].reshape(-1, 2 * W)
    off = start % W
    bits = (W - 1).bit_length()
    width = n + (1 << bits) - 1  # the widest slice the first select takes
    if width > 2 * W:  # W not a power of two: the top bit overshoots the pair
        x = jnp.pad(x, ((0, 0), (0, width - 2 * W)))
    for j in reversed(range(bits)):
        step = 1 << j
        keep = n + step - 1  # slots the remaining, smaller shifts can reach
        x = jnp.where(
            (off & step)[:, None] != 0, x[:, step:step + keep], x[:, :keep]
        )
    return x
