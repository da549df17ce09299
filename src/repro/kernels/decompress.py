"""Pallas TPU kernels: residual decompression + decompress-and-score.

Paper §4.5 decompresses with a 2^8-entry lookup table (CUDA thread per byte).
TPU re-derivation: each byte is copied to its output lanes by an exact
one-hot matmul, the b-bit fields are extracted with per-lane shift/mask ops
on the VPU, and the "LUT" degenerates to a (2^b,) weight vector selected
in-register (``_residuals``).

``decompress_and_score`` goes beyond the paper: it fuses stage-4 scoring into
the decompression pass, so decompressed residuals never reach HBM.  Grid is
over blocks of final candidate passages; the centroid rows arrive gathered
by XLA (see ``decompress_and_score_batched_pallas``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.constants import NEG
from repro.kernels.dispatch import resolve_interpret


def _unpack(packed: jax.Array, nbits: int) -> jax.Array:
    """(..., pd) integer bytes -> (..., pd * 8//nbits) int32 bucket indices,
    MSB-first within each byte.

    Mosaic has no lane interleave, so byte ``k`` is copied to output lanes
    ``[k*vpb, (k+1)*vpb)`` by a one-hot matmul — exact, since every output
    lane sums one byte value <= 255 — and each lane then shifts and masks
    out its own field.
    """
    vpb = 8 // nbits
    pd = packed.shape[-1]
    d = pd * vpb
    x = packed.reshape(-1, pd).astype(jnp.int32).astype(jnp.float32)
    src = jax.lax.broadcasted_iota(jnp.int32, (pd, d), 0)
    dst = jax.lax.broadcasted_iota(jnp.int32, (pd, d), 1)
    expand = (dst // vpb == src).astype(jnp.float32)
    rep = jnp.dot(x, expand, preferred_element_type=jnp.float32)
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, d), 1)
    shift = (vpb - 1 - lane % vpb) * nbits
    idx = (rep.astype(jnp.int32) >> shift) & (2**nbits - 1)
    return idx.reshape(*packed.shape[:-1], d)


def _residuals(packed: jax.Array, weights: jax.Array, nbits: int) -> jax.Array:
    """(R, pd) packed bytes -> (R, d) f32 residuals ``weights[bucket]``.

    The (2^b,) weight table is tiny: an unrolled select chain replaces the
    paper's lookup table — gather-free, pure VPU."""
    idx = _unpack(packed, nbits)
    out = jnp.zeros(idx.shape, jnp.float32)
    for v in range(weights.shape[0]):
        out = jnp.where(idx == v, weights[v], out)
    return out


# --------------------------------------------------------------------------
# Kernel 1: standalone decompression (paper's kernel, residuals -> floats)
# --------------------------------------------------------------------------
def _decompress_kernel(packed_ref, weights_ref, out_ref, *, nbits: int):
    out_ref[...] = _residuals(packed_ref[...], weights_ref[...][:, 0], nbits)


def decompress_residuals_pallas(
    packed: jax.Array,  # (n, pd) u8
    weights: jax.Array,  # (2^b,)
    *,
    nbits: int,
    row_block: int = 256,
    interpret: bool | None = None,
) -> jax.Array:
    interpret = resolve_interpret(interpret)
    n, pd = packed.shape
    vpb = 8 // nbits
    pad = (-n) % row_block
    if pad:
        packed = jnp.pad(packed, ((0, pad), (0, 0)))
    grid = ((n + pad) // row_block,)
    out = pl.pallas_call(
        functools.partial(_decompress_kernel, nbits=nbits),
        grid=grid,
        in_specs=[
            pl.BlockSpec((row_block, pd), lambda i: (i, 0)),
            pl.BlockSpec((weights.shape[0], 1), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((row_block, pd * vpb), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n + pad, pd * vpb), jnp.float32),
        interpret=interpret,
    )(packed, weights.astype(jnp.float32)[:, None])
    return out[:n]


# --------------------------------------------------------------------------
# Kernel 2 (beyond-paper): decompress + exact MaxSim, grid (B, doc_blocks)
# --------------------------------------------------------------------------
def _decompress_score_kernel(
    q_ref,  # (1, nq, d) f32 — this lane's query tile, resident per lane
    qmask_ref,  # (1, 1, nq)
    cent_ref,  # (1, BD*L, d) f32 — XLA-gathered centroid rows
    res_ref,  # (1, BD*L, pd) u8 packed residuals
    valid_ref,  # (1, BD*L, 1) i32
    weights_ref,  # (2^b, 1)
    out_ref,  # (1, BD, 1)
    *,
    nbits: int,
    L: int,
):
    emb = cent_ref[0] + _residuals(res_ref[0], weights_ref[...][:, 0], nbits)
    scores = jax.lax.dot_general(  # (BD*L, nq) — MXU matmul
        emb, q_ref[0], (((1,), (1,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    )
    scores = jnp.where(valid_ref[0] > 0, scores, NEG)
    per_q = scores.reshape(-1, L, scores.shape[-1]).max(axis=1)  # (BD, nq)
    out_ref[0] = (per_q * qmask_ref[0]).sum(axis=-1, keepdims=True)


def decompress_and_score_batched_pallas(
    q: jax.Array,  # (B, nq, d)
    q_mask: jax.Array,  # (B, nq)
    codes: jax.Array,  # (B, nd, L) i32
    packed_res: jax.Array,  # (B, nd, L, pd) u8
    tok_valid: jax.Array,  # (B, nd, L) bool
    centroids: jax.Array,  # (K, d)
    weights: jax.Array,  # (2^b,)
    *,
    nbits: int,
    doc_block: int = 8,
    interpret: bool | None = None,
) -> jax.Array:
    """Stage-4 decompress + exact MaxSim for a query batch: (B, nd) scores.

    The centroid rows ``centroids[code]`` are gathered by XLA (Mosaic has
    no row gather, and a (K, d) f32 table is 32 MiB at K=65,536 — more
    than the kernel's scoped VMEM); the packed residuals are expanded
    inside the kernel, so the decompressed residual tensor never reaches
    HBM.  ``L`` is padded to a multiple of 8 so the token axis folds into
    sublanes.
    """
    interpret = resolve_interpret(interpret)
    B, nd, L, pd = packed_res.shape
    d = centroids.shape[1]
    nq = q.shape[1]
    pad = (-nd) % doc_block
    lpad = (-L) % 8
    if pad or lpad:
        codes = jnp.pad(
            codes, ((0, 0), (0, pad), (0, lpad)), constant_values=-1
        )
        packed_res = jnp.pad(packed_res, ((0, 0), (0, pad), (0, lpad), (0, 0)))
        tok_valid = jnp.pad(tok_valid, ((0, 0), (0, pad), (0, lpad)))
    ndp, Lp = nd + pad, L + lpad
    rows = doc_block * Lp
    cent = centroids.astype(jnp.float32)[jnp.where(codes >= 0, codes, 0)]
    out = pl.pallas_call(
        functools.partial(_decompress_score_kernel, nbits=nbits, L=Lp),
        grid=(B, ndp // doc_block),
        in_specs=[
            pl.BlockSpec((1, nq, d), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1, 1, nq), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1, rows, d), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, rows, pd), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, rows, 1), lambda b, i: (b, i, 0)),
            pl.BlockSpec((weights.shape[0], 1), lambda b, i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, doc_block, 1), lambda b, i: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, ndp, 1), jnp.float32),
        interpret=interpret,
    )(
        q.astype(jnp.float32),
        q_mask.astype(jnp.float32)[:, None, :],
        cent.reshape(B, ndp * Lp, d),
        packed_res.reshape(B, ndp * Lp, pd),
        tok_valid.astype(jnp.int32).reshape(B, ndp * Lp, 1),
        weights.astype(jnp.float32)[:, None],
    )
    return out[:, :nd, 0]
