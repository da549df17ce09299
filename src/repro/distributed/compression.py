"""int8 gradient compression with error feedback.

Two layers:

* ``quantize``/``dequantize`` — per-tensor-block symmetric int8 with an f32
  scale per block of ``block`` values.  Pure math, used everywhere.
* ``compressed_psum`` — the collective: inside ``shard_map`` over the data
  axis, an all-reduce decomposed as all-to-all(int8) -> local dequant-sum ->
  all-gather(int8).  Bytes on the wire: 2 x size x 1B vs ~2 x size x 4B for
  a ring all-reduce in f32 -> ~4x compression.
* ``compress_decompress_with_feedback`` — single-device path used inside the
  jit train step: simulates the wire quantization and carries the
  quantization error into the next step (error feedback, 1-bit-Adam style),
  which restores convergence.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def quantize(x: jax.Array, block: int = 256):
    """x (f32, any shape) -> (int8 values, f32 scales, orig_size)."""
    flat = x.reshape(-1)
    n = flat.shape[0]
    pad = (-n) % block
    if pad:
        flat = jnp.pad(flat, (0, pad))
    blocks = flat.reshape(-1, block)
    scale = jnp.max(jnp.abs(blocks), axis=1, keepdims=True) / 127.0
    q = jnp.clip(jnp.round(blocks / jnp.maximum(scale, 1e-12)), -127, 127)
    return q.astype(jnp.int8), scale[:, 0], n


def dequantize(q: jax.Array, scale: jax.Array, n: int, shape):
    vals = q.astype(jnp.float32) * scale[:, None]
    return vals.reshape(-1)[:n].reshape(shape)


def compress_decompress_with_feedback(grads, ef_state):
    """Quantize+dequantize grads with error feedback; returns (grads, ef)."""
    if ef_state is None:
        ef_state = jax.tree.map(lambda g: jnp.zeros_like(g, jnp.float32), grads)

    def one(g, e):
        g32 = g.astype(jnp.float32) + e
        q, s, n = quantize(g32)
        deq = dequantize(q, s, n, g32.shape)
        return deq, g32 - deq

    flat_g, tdef = jax.tree.flatten(grads)
    flat_e = jax.tree.leaves(ef_state)
    out = [one(g, e) for g, e in zip(flat_g, flat_e)]
    new_g = jax.tree.unflatten(tdef, [o[0] for o in out])
    new_e = jax.tree.unflatten(tdef, [o[1] for o in out])
    return new_g, new_e


def compressed_psum(x: jax.Array, axis_name: str, block: int = 256):
    """All-reduce-mean of ``x`` over ``axis_name`` with int8 wire format.

    Must run inside ``shard_map``.  Decomposition: pad/split into
    ``n_dev`` chunks -> all_to_all(int8 + scales) -> local dequant + sum ->
    quantize chunk -> all_gather(int8) -> dequant.  Exact-size collectives;
    falls back to plain psum when the axis has a single member.
    """
    n_dev = jax.lax.axis_size(axis_name)
    if n_dev == 1:
        return x
    shape = x.shape
    flat = x.reshape(-1)
    n = flat.shape[0]
    pad = (-n) % (n_dev * block)
    if pad:
        flat = jnp.pad(flat, (0, pad))
    chunks = flat.reshape(n_dev, -1)  # (n_dev, chunk)
    q, s, cn = quantize(chunks.reshape(-1), block)
    q = q.reshape(n_dev, -1, block)
    s = s.reshape(n_dev, -1)
    # exchange: device i receives chunk i from every peer
    q_x = jax.lax.all_to_all(q, axis_name, split_axis=0, concat_axis=0, tiled=False)
    s_x = jax.lax.all_to_all(s, axis_name, split_axis=0, concat_axis=0, tiled=False)
    # local dequant + mean over peers
    vals = q_x.astype(jnp.float32) * s_x[..., None]  # (n_dev, blocks, block)
    summed = vals.mean(axis=0)  # (blocks, block)
    q2, s2, n2 = quantize(summed.reshape(-1), block)
    q_all = jax.lax.all_gather(q2, axis_name, axis=0)  # (n_dev, ...)
    s_all = jax.lax.all_gather(s2, axis_name, axis=0)
    out = (q_all.astype(jnp.float32) * s_all[..., None]).reshape(-1)[: n + pad]
    return out[:n].reshape(shape) if pad else out.reshape(shape)
