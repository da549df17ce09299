"""Mesh-parallel Lloyd k-means for the streaming build's pass 1.

``core.kmeans.kmeans_fit`` runs assignment + per-cluster statistics on one
device over the whole training sample.  Here the sample is split into a
FIXED number of equal blocks (``stat_blocks``, independent of the mesh
size), blocks are sharded over the mesh, and each Lloyd iteration runs

  ``shard_map``: per-block nearest-centroid assignment over this device's
  token blocks, per-block per-cluster partial sums/counts
  -> counts: ``psum`` over the mesh (integer-valued floats — exact, so the
     all-reduce order cannot matter)
  -> sums: :func:`repro.distributed.reduce.ordered_block_sum` — partials
     are all-gathered in global block order and summed sequentially,
     because a raw float ``psum`` would make the trained centroids drift
     with the device count (non-associative addition).

Net effect: for any device count dividing ``stat_blocks``, the trained
centroids are BITWISE identical to the single-device run — which is what
lets the build-determinism tests assert bit-identical indexes across
1-vs-4-device builds even when pass 1 is not frozen.

Init and empty-cluster reseeding mirror ``core.kmeans.kmeans_fit`` exactly
(same PRNG key discipline), so the two differ only in how float partial
sums are associated.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.compat import make_mesh, shard_map
from repro.core.kmeans import _assign_chunked
from repro.distributed.reduce import ordered_block_sum

#: mesh axis name the build collectives run over
BUILD_AXIS = "build"

#: fixed statistics granularity — every device count that divides this is
#: bitwise-reproducible against every other one (1/2/4/8 for the default)
DEFAULT_STAT_BLOCKS = 8


def build_mesh(n_devices: int | None = None):
    """A 1-D ``("build",)`` mesh over (up to) the local devices."""
    devices = jax.devices()
    n = len(devices) if n_devices is None else max(1, int(n_devices))
    if n > len(devices):
        raise ValueError(
            f"n_devices={n} exceeds the {len(devices)} visible devices"
        )
    return make_mesh((n,), (BUILD_AXIS,), devices=devices[:n])


def _block_stats(xb: jax.Array, wb: jax.Array, cents: jax.Array):
    """One block's per-cluster (sums, counts); padded rows carry weight 0.
    Assignment is ``core.kmeans``' chunked argmin, so no (block, K)
    distance matrix materializes at large K."""
    k = cents.shape[0]
    codes, _ = _assign_chunked(xb, cents)
    w = wb.astype(jnp.float32)
    sums = jax.ops.segment_sum(xb * w[:, None], codes, num_segments=k)
    counts = jax.ops.segment_sum(w, codes, num_segments=k)
    return sums, counts


@functools.lru_cache(maxsize=8)
def _fit_program(mesh, k: int, iters: int, stat_blocks: int):
    """Compiled Lloyd loop for one (mesh, k, iters, stat_blocks) tuple."""

    def local_stats(xb_local, wb_local, cents):
        # (local_blocks, block, d) -> per-block partials, then the two
        # deterministic combines described in the module docstring
        sums_b, counts_b = jax.lax.map(
            lambda xw: _block_stats(*xw, cents), (xb_local, wb_local)
        )
        sums = ordered_block_sum(sums_b, BUILD_AXIS)
        counts = jax.lax.psum(jnp.sum(counts_b, axis=0), BUILD_AXIS)
        return sums, counts

    stats = shard_map(
        local_stats,
        mesh=mesh,
        in_specs=(P(BUILD_AXIS), P(BUILD_AXIS), P()),
        out_specs=(P(), P()),
        check_rep=False,
    )

    def fit(xb, wb, x, key):
        n = x.shape[0]
        init_idx = jax.random.choice(key, n, shape=(k,), replace=n < k)
        cents0 = x[init_idx]

        def step(cents, key_i):
            sums, counts = stats(xb, wb, cents)
            means = sums / jnp.maximum(counts, 1.0)[:, None]
            # Re-seed empties from random data points (same fix-up as
            # core.kmeans.kmeans_fit, same key schedule).
            reseed = x[jax.random.choice(key_i, n, shape=(k,))]
            return jnp.where((counts > 0)[:, None], means, reseed), None

        keys = jax.random.split(key, iters)
        cents, _ = jax.lax.scan(step, cents0, keys)
        return cents

    return jax.jit(fit)


def kmeans_fit_mesh(
    x,
    k: int,
    *,
    key: jax.Array,
    iters: int = 8,
    mesh=None,
    stat_blocks: int = DEFAULT_STAT_BLOCKS,
) -> jax.Array:
    """Train ``(k, d)`` centroids on ``x`` with mesh-parallel Lloyd steps.

    Bitwise invariant to the mesh device count for any count dividing
    ``stat_blocks`` (see module docstring).  ``mesh=None`` builds a 1-D
    mesh over all local devices.
    """
    x = jnp.asarray(x, jnp.float32)
    n, d = x.shape
    if mesh is None:
        mesh = build_mesh()
    n_dev = mesh.devices.size
    if stat_blocks % n_dev:
        raise ValueError(
            f"stat_blocks={stat_blocks} must be divisible by the mesh "
            f"device count ({n_dev}) — and kept CONSTANT across runs that "
            "must be bit-identical"
        )
    block = -(-n // stat_blocks)  # ceil
    pad = stat_blocks * block - n
    xb = jnp.pad(x, ((0, pad), (0, 0))).reshape(stat_blocks, block, d)
    wb = jnp.pad(jnp.ones((n,), jnp.float32), (0, pad)).reshape(
        stat_blocks, block
    )
    return _fit_program(mesh, int(k), int(iters), int(stat_blocks))(
        xb, wb, x, key
    )
