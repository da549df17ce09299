"""The plain references, run after the measured window.

Independent of the program: they import nothing of ``repro`` and take no
array the program made.  Passages come from the benchmark's own generator
(``corpus.Corpus.payload_block``, the program that quantized the payload the
program was given), are decompressed as centroid plus dequantized residual,
and are scored in float32 at ``HIGHEST`` precision.

* :func:`exhaustive_topk`: MaxSim over every passage, in blocks (the
  ranking ``recall_k`` is measured against);
* :func:`plaid_topk`: PLAID's own four stages, written plainly over the
  benchmark's inverted lists (``corpus.load_inverted_lists``): stage-1
  centroid scores and the top-``nprobe`` probe of each query token, the
  union of the probed lists truncated at ``candidate_cap`` (lowest pids
  first), the centroid interaction over each candidate's distinct codes
  with the centroids whose best query-token score is under ``t_cs`` left
  out, the top ``ndocs``, the same interaction unpruned, the top
  ``max(ndocs / 4, k)``, then exact MaxSim and the top ``k``;
* :func:`score_pids`: the exact MaxSim of given passages.

Where a configuration splits its passages over ``shards`` chips
(:func:`shard_ranges`), :func:`plaid_topk` runs stages 1 to 3 on each
shard's pid range alone, with the cap clamped to the shard's size, and
takes the top ``k`` of the union of every shard's finalists: each shard's
own top ``k`` merged by score, as a corpus split over chips computes it.

``low=True`` (``control=True`` for the exhaustive pass) computes the same
in bfloat16: the next precision below the configuration's, the control
that the comparison in ``check`` has to refuse.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from plaidbench.corpus import BLOCK, Corpus

NEG = -1e30
#: Passages scored in one step of a block (bounds the (x, p, l) score tile).
SUB = 256


def _maxsim(q, emb, valid, dtype):
    """q (nQ, nq, d), emb (P, L, d), valid (P, L) -> (nQ, P) MaxSim."""
    nQ, nq, d = q.shape
    x = q.reshape(nQ * nq, d).astype(dtype)
    e = emb.astype(dtype)
    s = jnp.einsum(
        "xd,pld->xpl", x, e, precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    )
    s = jnp.where(valid[None], s, NEG).max(axis=-1)  # (x, P)
    return s.reshape(nQ, nq, -1).sum(axis=1)


@functools.partial(jax.jit, static_argnames=("k", "control"))
def _block_topk(concepts, weights, q, ids, buckets, valid, pids, run, *, k, control):
    emb = concepts[ids] + weights[buckets]
    P = ids.shape[0]

    def sub(i):
        sl = lambda a: jax.lax.dynamic_slice_in_dim(a, i * SUB, SUB)  # noqa: E731
        out = [_maxsim(q, sl(emb), sl(valid), jnp.float32)]
        if control:
            out.append(_maxsim(q, sl(emb), sl(valid), jnp.bfloat16))
        return jnp.stack(out)  # (n_prec, nQ, SUB)

    s = jax.lax.map(sub, jnp.arange(P // SUB))  # (P/SUB, n_prec, nQ, SUB)
    s = jnp.moveaxis(s, 0, 2).reshape(s.shape[1], q.shape[0], P)
    s = jnp.where(pids[None, None] >= 0, s, NEG)
    out = []
    for j, (rs, rp) in enumerate(run):
        cs = jnp.concatenate([rs, s[j]], axis=1)
        cp = jnp.concatenate([rp, jnp.broadcast_to(pids, s[j].shape)], axis=1)
        ts, ti = jax.lax.top_k(cs, k)
        out.append((ts, jnp.take_along_axis(cp, ti, axis=1)))
    return out


def exhaustive_topk(corpus: Corpus, qs: np.ndarray, k: int, *, control=False):
    """Exact top-``k`` (scores, pids) of every query over the whole corpus;
    with ``control``, a list of two: float32, then bfloat16 operands."""
    n = len(qs)
    run = [
        (jnp.full((n, k), NEG, jnp.float32), jnp.full((n, k), -1, jnp.int32))
        for _ in range(2 if control else 1)
    ]
    q = jnp.asarray(qs)
    for _, _, pids in corpus.blocks(np.arange(corpus.spec.passages, dtype=np.int32)):
        ids, buckets, valid = corpus.payload_block(pids)
        run = _block_topk(
            corpus.concepts, corpus.weights, q, ids, buckets, valid, pids, run,
            k=k, control=control,
        )
    out = [(np.asarray(s), np.asarray(p)) for s, p in run]
    return out if control else out[0]


@functools.partial(jax.jit, static_argnames="low")
def _rescore(concepts, weights, q, ids, buckets, valid, *, low=False):
    """q (P, nq, d) against its own passage (P, L, d): (P,) MaxSim."""
    emb = concepts[ids] + weights[buckets]
    dt = jnp.bfloat16 if low else jnp.float32
    s = jnp.einsum(
        "pqd,pld->pql", q.astype(dt), emb.astype(dt),
        precision=jax.lax.Precision.HIGHEST, preferred_element_type=jnp.float32,
    )
    return jnp.where(valid[:, None, :], s, NEG).max(axis=-1).sum(axis=-1)


def score_pids(corpus: Corpus, qs: np.ndarray, pids: np.ndarray, *, low=False) -> np.ndarray:
    """Reference MaxSim of each query (nQ, ...) against each of its own
    returned pids (nQ, k); NaN where a pid is not a passage."""
    nQ, k = pids.shape
    flat = pids.reshape(-1)
    qidx = np.repeat(np.arange(nQ), k)
    real = (flat >= 0) & (flat < corpus.spec.passages)
    out = np.full(flat.shape, np.nan, np.float64)
    sel = np.flatnonzero(real)
    for i, m, blk in corpus.blocks(flat[sel]):
        ids, buckets, valid = corpus.payload_block(blk)
        qi = np.zeros(BLOCK, np.int64)
        qi[:m] = qidx[sel[i : i + m]]
        s = _rescore(
            corpus.concepts, corpus.weights, jnp.asarray(qs[qi]), ids, buckets, valid, low=low,
        )
        out[sel[i : i + m]] = np.asarray(s)[:m]
    return out.reshape(nQ, k)


# --------------------------------------------------------------------------
# PLAID's four stages, plainly
# --------------------------------------------------------------------------
@functools.partial(
    jax.jit, static_argnames=("nprobe", "cap", "n2", "n3", "list_len", "width", "low"),
)
def _finalists(concepts, code_pids, code_off, pid_codes, pid_off, q, t_cs, *,
               nprobe, cap, n2, n3, list_len, width, low):
    """One query (nq, d) -> its (n3,) stage-3 finalists (-1 pads) and the
    number of distinct passages its probes reached (before the cap)."""
    n_pass = pid_off.shape[0] - 1
    if low:
        s = jnp.einsum("id,kd->ik", q.astype(jnp.bfloat16), concepts.astype(jnp.bfloat16),
                       preferred_element_type=jnp.float32).astype(jnp.bfloat16)
        s = s.astype(jnp.float32)
    else:
        s = jnp.einsum("id,kd->ik", q, concepts, precision=jax.lax.Precision.HIGHEST)
    # stage 1: each query token's top-nprobe centroids, the union of their lists
    probes = jax.lax.top_k(s, nprobe)[1].reshape(-1)
    start, stop = code_off[probes], code_off[probes + 1]
    pos = jnp.arange(list_len)
    inl = pos[None, :] < (stop - start)[:, None]
    reached = jnp.where(inl, code_pids[jnp.where(inl, start[:, None] + pos, 0)], n_pass)
    srt = jnp.sort(reached.reshape(-1))
    n_reached = ((srt[1:] != srt[:-1]) & (srt[1:] < n_pass)).sum() + (srt[0] < n_pass)
    cand = jnp.unique(srt, size=cap, fill_value=n_pass)  # lowest pids first
    real = cand < n_pass
    # stages 2 and 3: centroid interaction over each candidate's distinct codes
    safe = jnp.where(real, cand, 0)
    first = pid_off[safe]
    n_codes = jnp.where(real, pid_off[safe + 1] - first, 0)
    col = jnp.arange(width)
    has = col[None, :] < n_codes[:, None]
    codes = jnp.where(has, pid_codes[jnp.where(has, first[:, None] + col, 0)], 0)
    tok = s.T[codes]  # (cap, width, nq)
    kept = has & (s.max(axis=0) >= t_cs)[codes]

    def interaction(mask):
        best = jnp.where(mask[..., None], tok, NEG).max(axis=1)  # (cap, nq)
        return jnp.maximum(best, 0.0).sum(axis=-1)

    a2 = jnp.where(real, interaction(kept), NEG)
    i2 = jax.lax.top_k(a2, n2)[1]
    c2 = cand[i2]
    a3 = jnp.where(c2 < n_pass, interaction(has)[i2], NEG)
    fin = c2[jax.lax.top_k(a3, n3)[1]]
    return jnp.where(fin < n_pass, fin, -1), n_reached


def shard_ranges(passages: int, shards: int) -> list[tuple[int, int]]:
    """The pid range ``[lo, hi)`` of each of ``shards`` contiguous shards of
    ``per = ceil(passages / shards)`` passages; the last may hold fewer."""
    per = -(-passages // shards)
    return [(min(i * per, passages), min((i + 1) * per, passages)) for i in range(shards)]


def shard_cap(search: dict, passages: int, shards: int = 1) -> int:
    """The candidate cap of one shard: ``candidate_cap`` clamped to the
    shard's size, as the program clamps it."""
    return min(search["candidate_cap"], max(-(-passages // shards), 2))


def _shard_lists(lists: dict, ranges: list, per: int) -> list[dict]:
    """The inverted lists of each shard's pids alone, in local pids
    ``0 .. per`` (a shard past its last passage holds no codes), every
    shard's arrays padded to one shape so that one program runs them all."""
    cp, co = lists["code_pids"], lists["code_off"]
    pc, po = lists["pid_codes"], lists["pid_off"]
    parts = []
    for lo, hi in ranges:
        mine = (cp >= lo) & (cp < hi)  # a code's list ascends by pid: one slice of it
        kept = np.zeros(len(cp) + 1, np.int64)
        np.cumsum(mine, out=kept[1:])
        pid_off = np.full(per + 1, po[hi] - po[lo], np.int64)
        pid_off[: hi - lo + 1] = po[lo : hi + 1] - po[lo]
        parts.append({
            "pid_codes": pc[po[lo] : po[hi]], "pid_off": pid_off.astype(np.int32),
            "code_pids": (cp[mine] - lo).astype(np.int32),
            "code_off": kept[co].astype(np.int32),
        })
    for key in ("pid_codes", "code_pids"):
        n = max(1, *(len(p[key]) for p in parts))
        for p in parts:
            p[key] = np.pad(p[key], (0, n - len(p[key])))
    return parts


def plaid_topk(corpus: Corpus, lists: dict, qs: np.ndarray, search: dict, *,
               shards: int = 1, low=False):
    """PLAID's top-``k`` (scores, pids) of each query (nQ, nq, d) at the
    configuration's ``search`` settings, and each query's count of distinct
    passages reached by its probes (above the cap, the cap cut), the
    largest over the shards where ``shards`` > 1 (:func:`shard_cap`)."""
    n_pass = corpus.spec.passages
    ranges = shard_ranges(n_pass, shards)
    cap = shard_cap(search, n_pass, shards)
    n2 = min(search["ndocs"], cap)
    n3 = min(max(search["ndocs"] // 4, search["k"]), n2)
    parts = [lists] if shards == 1 else _shard_lists(lists, ranges, -(-n_pass // shards))
    static = dict(
        nprobe=search["nprobe"], cap=cap, n2=n2, n3=n3, low=low,
        list_len=max(int(np.diff(p["code_off"]).max()) for p in parts),
        width=max(int(np.diff(p["pid_off"]).max()) for p in parts),
    )
    fin, reached = [], []
    for (lo, _), part in zip(ranges, parts):
        dev = {k: jnp.asarray(v) for k, v in part.items()}
        f, r = [], []
        for q in qs:
            a, b = _finalists(
                corpus.concepts, dev["code_pids"], dev["code_off"], dev["pid_codes"],
                dev["pid_off"], jnp.asarray(q), jnp.float32(search["t_cs"]), **static,
            )
            f.append(a)
            r.append(b)
        f = np.asarray(jnp.stack(f))
        fin.append(np.where(f >= 0, f + lo, -1))
        reached.append(np.asarray(jnp.stack(r)))
    fin = np.concatenate(fin, axis=1)
    exact = score_pids(corpus, qs, fin, low=low)
    exact = np.where(fin >= 0, exact, -np.inf)
    order = np.argsort(-exact, axis=1, kind="stable")[:, : search["k"]]
    top_s = np.take_along_axis(exact, order, axis=1)
    top_p = np.take_along_axis(fin, order, axis=1)
    return top_s, np.where(np.isfinite(top_s), top_p, -1), np.max(reached, axis=0)
