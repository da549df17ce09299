"""Batched Lloyd k-means in pure JAX (index-build substrate).

ColBERTv2 sets the number of centroids proportional to sqrt(#embeddings)
(``16 * sqrt(n)`` rounded to a power of two).  We train on a sample of token
embeddings with chunked assignment so the (n, K) distance matrix never
materializes for large n.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np


def num_centroids_for(n_tokens: int, cap: int = 2**18) -> int:
    """ColBERTv2 heuristic: next power of two >= 16*sqrt(n), capped."""
    k = 2 ** int(math.ceil(math.log2(max(16.0 * math.sqrt(max(n_tokens, 1)), 2.0))))
    return int(min(k, cap, max(2, n_tokens)))


#: Bound on one assignment chunk's (rows, K) f32 distance tile: 256 MiB.
#: Row-wise math, so the chunk size never changes a code.
_CHUNK_ELEMS = 1 << 26


def assign_chunk_rows(k: int, chunk: int = 16384) -> int:
    """Rows per assignment chunk: ``chunk``, shrunk (by powers of two, not
    below 512) until the (rows, k) distance tile fits ``_CHUNK_ELEMS``."""
    while chunk > 512 and chunk * k > _CHUNK_ELEMS:
        chunk //= 2
    return chunk


@functools.partial(jax.jit, static_argnames=("chunk",))
def _assign_chunked(x: jax.Array, centroids: jax.Array, chunk: int = 16384):
    """argmin_c ||x - c||^2 computed in row chunks; returns (codes, min_d2).

    ``chunk`` is capped by :func:`assign_chunk_rows`, so at K = 65,536 the
    distance tile stays at 256 MiB instead of 4 GiB."""
    chunk = assign_chunk_rows(centroids.shape[0], chunk)
    n = x.shape[0]
    nchunks = (n + chunk - 1) // chunk
    xp = jnp.pad(x, ((0, nchunks * chunk - n), (0, 0)))
    c_sq = jnp.sum(centroids**2, axis=-1)

    def body(i, carry):
        codes, dists = carry
        rows = jax.lax.dynamic_slice_in_dim(xp, i * chunk, chunk, axis=0)
        d2 = c_sq[None, :] - 2.0 * (rows @ centroids.T)
        idx = jnp.argmin(d2, axis=-1).astype(jnp.int32)
        best = jnp.take_along_axis(d2, idx[:, None], axis=-1)[:, 0]
        codes = jax.lax.dynamic_update_slice_in_dim(codes, idx, i * chunk, 0)
        dists = jax.lax.dynamic_update_slice_in_dim(dists, best, i * chunk, 0)
        return codes, dists

    codes = jnp.zeros((nchunks * chunk,), jnp.int32)
    dists = jnp.zeros((nchunks * chunk,), jnp.float32)
    codes, dists = jax.lax.fori_loop(0, nchunks, body, (codes, dists))
    return codes[:n], dists[:n]


@functools.partial(jax.jit, static_argnames=("k", "iters", "chunk"))
def kmeans_fit(
    x: jax.Array,
    k: int,
    *,
    key: jax.Array,
    iters: int = 8,
    chunk: int = 16384,
) -> jax.Array:
    """Lloyd iterations; empty clusters are re-seeded from random points."""
    x = x.astype(jnp.float32)
    n = x.shape[0]
    init_idx = jax.random.choice(key, n, shape=(k,), replace=n < k)
    centroids = x[init_idx]

    def step(carry, key_i):
        cents = carry
        codes, _ = _assign_chunked(x, cents, chunk=chunk)
        sums = jax.ops.segment_sum(x, codes, num_segments=k)
        counts = jax.ops.segment_sum(jnp.ones((n,), jnp.float32), codes, k)
        means = sums / jnp.maximum(counts, 1.0)[:, None]
        # Re-seed empties from random data points (standard Lloyd fix-up).
        reseed = x[jax.random.choice(key_i, n, shape=(k,))]
        cents = jnp.where((counts > 0)[:, None], means, reseed)
        return cents, None

    keys = jax.random.split(key, iters)
    centroids, _ = jax.lax.scan(step, centroids, keys)
    return centroids


def train_centroids(
    embeddings: np.ndarray | jax.Array,
    k: int | None = None,
    *,
    seed: int = 0,
    sample: int = 1 << 18,
    iters: int = 8,
) -> jax.Array:
    """Index-build entry point: sample -> fit -> return (k, d) centroids."""
    emb = jnp.asarray(embeddings, dtype=jnp.float32)
    n = emb.shape[0]
    if k is None:
        k = num_centroids_for(n)
    # Independent keys for the two draws: reusing one key would correlate
    # WHICH tokens train with WHERE the Lloyd iteration starts (the sampled
    # rows and the init rows come from the same permutation stream).
    key_sample, key_fit = jax.random.split(jax.random.PRNGKey(seed))
    if n > sample:
        idx = jax.random.choice(key_sample, n, shape=(sample,), replace=False)
        emb = emb[idx]
    return kmeans_fit(emb, k, key=key_fit, iters=iters)
