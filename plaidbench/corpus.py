"""The cells' corpus, index payload and queries, made on the device from seeds.

A copy of the concept model of the program's synthetic corpus
(``repro.data.synthetic.CorpusStream``), kept with the benchmark so that no
later change to the program moves the yardstick:

* ``K`` unit-norm concept vectors are the centroids (where k-means would
  converge on this corpus); each topic owns ``K / topics`` of them;
* passage lengths are log-normal with mean ``mean_len``, clipped to
  ``[min_len, doc_maxlen]``;
* a passage draws ``ceil(len / repeat)`` concepts of its topic and each of
  its tokens repeats one of them; a token is its concept plus relative
  noise ``noise``, normalized; its code is its concept;
* the residual (token minus centroid) is quantized per dimension by the
  ColBERTv2 quantile codec (copied from ``repro.core.residual_codec``) and
  packed ``8 // nbits`` values to a byte, most significant bits first.

Every passage is a pure function of ``(corpus_seed, pid)``, so any set of
passages can be made on its own: the index payload once, the queries from
passages drawn by the traffic seed, and the reference's exhaustive pass,
all through the one jitted :func:`Corpus.payload_block` at one block shape,
so that the reference decompresses bit for bit what the program was given.

The build also keeps the benchmark's own inverted lists beside the program's
saved index (``reference.npz``): the distinct codes of each passage and the
passages of each code, made here from the generator's codes, for the plain
PLAID reference (``reference.plaid_topk``).
"""
from __future__ import annotations

import dataclasses
import functools
import json
import os
import pathlib
import shutil

import jax
import jax.numpy as jnp
import numpy as np

#: Bumped whenever the generator or the cached layout changes.
FORMAT_VERSION = 2
#: Passages per call of :func:`Corpus.payload_block` (one compiled shape).
BLOCK = 2048
#: Passages whose residuals fit the codec's quantiles.
CODEC_SAMPLE = 2048
CACHE_DIR = pathlib.Path(__file__).resolve().parent / ".index_cache"
#: The benchmark's own inverted lists, saved beside the program's index.
REFERENCE_FILE = "reference.npz"


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from a whole number of up to 64 bits (``PRNGKey`` alone
    keeps only the low 32)."""
    seed = int(seed)
    key = jax.random.PRNGKey(0)
    key = jax.random.fold_in(key, seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


@dataclasses.dataclass(frozen=True)
class CorpusSpec:
    """The corpus half of a configuration file."""

    corpus: str
    corpus_seed: int
    passages: int
    dim: int
    nbits: int
    centroids: int
    topics: int
    mean_len: float
    len_sigma: float
    min_len: int
    doc_maxlen: int
    repeat: float
    noise: float
    query_noise: float
    q_len: int

    @classmethod
    def from_config(cls, cfg: dict) -> "CorpusSpec":
        return cls(**{f.name: cfg[f.name] for f in dataclasses.fields(cls)})

    @property
    def pool(self) -> int:
        return self.centroids // self.topics

    def cache_key(self) -> str:
        return f"{self.corpus}-seed{self.corpus_seed}-v{FORMAT_VERSION}"


# --------------------------------------------------------------------------
# codec arithmetic (copied from repro.core.residual_codec; the fit, with
# ColBERTv2's quantile rule, is in ``_codec`` below)
# --------------------------------------------------------------------------
def bucketize(cutoffs: jax.Array, residuals: jax.Array) -> jax.Array:
    return jnp.searchsorted(cutoffs, residuals, side="right").astype(jnp.uint8)


def pack(indices: jax.Array, nbits: int) -> jax.Array:
    """(..., dim) b-bit values -> (..., dim * b / 8) uint8, MSB first."""
    vpb = 8 // nbits
    *lead, dim = indices.shape
    grouped = indices.reshape(*lead, dim // vpb, vpb).astype(jnp.uint32)
    shifts = jnp.arange(vpb - 1, -1, -1, dtype=jnp.uint32) * nbits
    return (grouped << shifts).sum(axis=-1).astype(jnp.uint8)


# --------------------------------------------------------------------------
# the generator: jitted on the spec (static), every table an argument
# --------------------------------------------------------------------------
def _tokens(spec: CorpusSpec, k_pass, concepts, pids, lens):
    """(P,) pids -> concept ids (P, L) and unit-norm tokens (P, L, d)."""
    s = spec
    L = s.doc_maxlen
    n_slots = -(-L // int(s.repeat)) + 1

    def one(pid, ln):
        k_topic, k_slot, k_pick, k_noise = jax.random.split(
            jax.random.fold_in(k_pass, pid), 4
        )
        topic = jax.random.randint(k_topic, (), 0, s.topics)
        m = jnp.ceil(ln / s.repeat).astype(jnp.int32)
        slots = jax.random.randint(k_slot, (n_slots,), 0, s.pool)
        r = (jax.random.uniform(k_pick, (L,)) * m).astype(jnp.int32)
        ids = topic * s.pool + slots[jnp.minimum(r, n_slots - 1)]
        return ids, jax.random.normal(k_noise, (L, s.dim), jnp.float32)

    ids, u = jax.vmap(one)(pids, lens)
    e = concepts[ids] + (s.noise / np.sqrt(s.dim)) * u
    return ids, e / jnp.linalg.norm(e, axis=-1, keepdims=True)


@functools.partial(jax.jit, static_argnums=0)
def _concepts(spec: CorpusSpec, key):
    c = jax.random.normal(key, (spec.centroids, spec.dim), jnp.float32)
    return c / jnp.linalg.norm(c, axis=-1, keepdims=True)


@functools.partial(jax.jit, static_argnums=0)
def _codec(spec: CorpusSpec, k_pass, concepts, lens):
    """Quantiles of the valid residuals of passages ``0 .. len(lens)``."""
    pids = jnp.arange(lens.shape[0], dtype=jnp.int32)
    ids, tok = _tokens(spec, k_pass, concepts, pids, lens)
    res = tok - concepts[ids]
    valid = jnp.arange(spec.doc_maxlen)[None, :] < lens[:, None]
    flat = jnp.where(valid[..., None], res, jnp.nan).reshape(-1)
    n = 2**spec.nbits
    cutoffs = jnp.nanquantile(flat, jnp.arange(1, n) / n)
    weights = jnp.nanquantile(flat, (jnp.arange(n) + 0.5) / n)
    return cutoffs, weights


@functools.partial(jax.jit, static_argnums=0)
def _payload_block(spec: CorpusSpec, k_pass, concepts, cutoffs, all_lens, pids):
    safe = jnp.maximum(pids, 0)
    lens = jnp.where(pids >= 0, all_lens[safe], 0)
    ids, tok = _tokens(spec, k_pass, concepts, safe, lens)
    buckets = bucketize(cutoffs, tok - concepts[ids])
    valid = jnp.arange(spec.doc_maxlen)[None, :] < lens[:, None]
    return ids, buckets, valid


@functools.partial(jax.jit, static_argnums=0)
def _queries(spec: CorpusSpec, k_pass, concepts, all_lens, key, pids, pos):
    _, tok = _tokens(spec, k_pass, concepts, pids, all_lens[pids])
    q = jnp.take_along_axis(tok, pos[..., None], axis=1)  # (Q, q_len, d)
    u = jax.random.normal(key, q.shape, jnp.float32)
    q = q + (spec.query_noise / np.sqrt(spec.dim)) * u
    return q / jnp.linalg.norm(q, axis=-1, keepdims=True)


class Corpus:
    """Passages of one :class:`CorpusSpec`, made on the default device."""

    def __init__(self, spec: CorpusSpec):
        self.spec = s = spec
        rng = np.random.default_rng((s.corpus_seed, 0))
        mu = np.log(s.mean_len) - s.len_sigma**2 / 2
        self.lens = np.clip(
            np.rint(rng.lognormal(mu, s.len_sigma, s.passages)),
            s.min_len, s.doc_maxlen,
        ).astype(np.int32)
        self._lens_dev = jnp.asarray(self.lens)
        k_concepts, self._k_pass = jax.random.split(seed_key(s.corpus_seed))
        self.concepts = _concepts(s, k_concepts)
        self.cutoffs, self.weights = _codec(
            s, self._k_pass, self.concepts,
            jnp.asarray(self.lens[: min(CODEC_SAMPLE, s.passages)]),
        )

    @property
    def num_tokens(self) -> int:
        return int(self.lens.sum(dtype=np.int64))

    def payload_block(self, pids: jax.Array):
        """(BLOCK,) pids (-1 pads) -> codes (BLOCK, L) i32, buckets
        (BLOCK, L, d) u8 and valid (BLOCK, L) bool.  The one program that
        quantizes: the payload and the reference both call it at BLOCK."""
        return _payload_block(
            self.spec, self._k_pass, self.concepts, self.cutoffs,
            self._lens_dev, pids,
        )

    def blocks(self, pids: np.ndarray):
        """``pids`` in BLOCK-sized device arrays, the last padded with -1."""
        for i in range(0, len(pids), BLOCK):
            b = np.full(BLOCK, -1, np.int32)
            chunk = pids[i : i + BLOCK]
            b[: len(chunk)] = chunk
            yield i, len(chunk), jnp.asarray(b)

    def queries(self, n: int, seed: int) -> tuple[np.ndarray, np.ndarray]:
        """``n`` queries, each ``q_len`` noisy tokens of a passage drawn
        uniformly over all pids (``repro.data.synthetic.queries_from_docs``'s
        rule); returns host (n, q_len, d) float32 queries and source pids."""
        s = self.spec
        rng = np.random.default_rng((int(seed), 1))
        pids = rng.integers(0, s.passages, n).astype(np.int32)
        pos = (rng.random((n, s.q_len)) * self.lens[pids][:, None]).astype(np.int32)
        q = _queries(
            s, self._k_pass, self.concepts, self._lens_dev,
            jax.random.fold_in(seed_key(seed), 1), jnp.asarray(pids),
            jnp.asarray(pos),
        )
        return np.asarray(q), pids

    # ---- the program's index -------------------------------------------
    def payload(self):
        """Host CSR payload of the whole corpus: flat codes (Nt,) i32, packed
        residuals (Nt, d*b/8) u8, the sorted unique (code, pid) rows, and the
        benchmark's own inverted lists (:func:`inverted_lists`)."""
        s = self.spec
        packed_fn = jax.jit(functools.partial(pack, nbits=s.nbits))
        # written in place, block by block: the host holds the payload once
        tok_off = np.zeros(s.passages + 1, np.int64)
        np.cumsum(self.lens, out=tok_off[1:])
        codes = np.empty(tok_off[-1], np.int32)
        packed = np.empty((tok_off[-1], s.dim * s.nbits // 8), np.uint8)
        pairs_c, pairs_p = [], []
        for i, n, pids in self.blocks(np.arange(s.passages, dtype=np.int32)):
            ids, buckets, valid = self.payload_block(pids)
            ids, valid = np.asarray(ids)[:n], np.asarray(valid)[:n]
            t0, t1 = tok_off[i], tok_off[i + n]
            codes[t0:t1] = ids[valid]
            packed[t0:t1] = np.asarray(packed_fn(buckets))[:n][valid]
            srt = np.sort(np.where(valid, ids, s.centroids), axis=1)
            first = np.ones_like(srt, bool)
            first[:, 1:] = srt[:, 1:] != srt[:, :-1]
            first &= srt < s.centroids
            rows, cols = np.nonzero(first)
            pairs_c.append(srt[rows, cols])
            pairs_p.append(rows.astype(np.int32) + i)
        pc, pp = np.concatenate(pairs_c), np.concatenate(pairs_p)
        order = np.argsort(pc, kind="stable")  # pids already ascend per code
        pairs = np.stack([pc[order].astype(np.int64), pp[order].astype(np.int64)], 1)
        return codes, packed, pairs, inverted_lists(s, pc, pp, order)


def inverted_lists(spec: CorpusSpec, pc: np.ndarray, pp: np.ndarray, order: np.ndarray) -> dict:
    """From the distinct (code, pid) rows in pid order: each passage's
    distinct codes (``pid_codes`` from ``pid_off[p]``) and each code's
    passages in ascending pid order (``code_pids`` from ``code_off[c]``)."""
    pid_off = np.zeros(spec.passages + 1, np.int64)
    np.cumsum(np.bincount(pp, minlength=spec.passages), out=pid_off[1:])
    code_off = np.zeros(spec.centroids + 1, np.int64)
    np.cumsum(np.bincount(pc, minlength=spec.centroids), out=code_off[1:])
    return {
        "pid_codes": pc.astype(np.int32), "pid_off": pid_off.astype(np.int32),
        "code_pids": pp[order].astype(np.int32), "code_off": code_off.astype(np.int32),
    }


def load_inverted_lists(corpus: Corpus) -> dict:
    """The inverted lists :func:`load_or_build` saved for this corpus."""
    with np.load(CACHE_DIR / corpus.spec.cache_key() / REFERENCE_FILE) as z:
        return {k: z[k] for k in z.files}


def _layout(backend: str, n_shards: int | None) -> str:
    """A saved index's layout: the program's code that reads it (backends
    that share a format share it) and, for a sharded save, its shards."""
    from repro.retrieval import registry

    reader = registry.get_backend(backend).load.__func__.__qualname__
    return reader + ("" if n_shards is None else f" x {n_shards} shards")


def _saved_mismatch(path: pathlib.Path, spec: CorpusSpec, backend: str, shards: int) -> list[str]:
    """How the index saved at ``path`` differs from what the configuration
    asks for: each differing ``CorpusSpec`` field, and the layout."""
    with open(path / "corpus.json") as f:
        saved = json.load(f)
    want = dataclasses.asdict(spec)
    out = [
        f"{k}: saved {saved.get(k)!r}, configuration {want.get(k)!r}"
        for k in sorted(set(saved) | set(want)) if saved.get(k) != want.get(k)
    ]
    with open(path / "retriever.json") as f:
        saved_backend = json.load(f)["backend"]
    with open(path / "manifest.json") as f:
        saved_shards = json.load(f).get("n_shards")
    has = _layout(saved_backend, saved_shards)
    need = _layout(backend, None if saved_shards is None else shards)
    if has != need:
        out.append(f"layout: saved {has} (backend {saved_backend}), "
                   f"configuration {need} (backend {backend}, shards {shards})")
    return out


def load_or_build(corpus: Corpus, backend: str, params, log=print, *, shards: int = 1):
    """The program's retriever over this corpus: loaded with the program's
    own ``retrieval.load`` from the cache under ``plaidbench/``, or, on the
    first run in a checkout, assembled through ``core.index.assemble_index``
    and saved there with the program's own save (a sharded backend split
    over the configuration's ``shards`` where it sets more than one).  A
    saved index whose
    corpus fields (``corpus.json``) or layout (``retriever.json``, and the
    shard count of a sharded save against the configuration's ``shards``)
    differ from the configuration's is refused, never loaded."""
    from repro import retrieval
    from repro.core.index import assemble_index

    s = corpus.spec
    path = CACHE_DIR / s.cache_key()
    if (path / "retriever.json").exists():
        diff = _saved_mismatch(path, s, backend, shards)
        if diff:
            raise ValueError(
                f"plaidbench: the index saved at {path} was built for another configuration "
                f"({'; '.join(diff)}); delete it, or give the corpus another name"
            )
        log(f"[index] load {path.name}")
        return retrieval.load(str(path), backend=backend, params=params), False
    log(f"[index] build {path.name}: {s.passages} passages, {corpus.num_tokens} tokens")
    codes, packed, pairs, lists = corpus.payload()
    # copies: the program's arrays are freed before the reference runs
    index = assemble_index(
        jnp.array(corpus.concepts, copy=True), codes, packed, corpus.lens,
        cutoffs=jnp.array(corpus.cutoffs, copy=True),
        weights=jnp.array(corpus.weights, copy=True), nbits=s.nbits,
        pairs=pairs,
    )
    del codes, packed, pairs
    split = {"n_shards": shards} if shards > 1 else {}  # else the program takes every device
    r = retrieval.from_index(index, backend=backend, params=params, **split)
    tmp = path.with_name(f"{path.name}.tmp{os.getpid()}")
    shutil.rmtree(tmp, ignore_errors=True)
    os.makedirs(tmp.parent, exist_ok=True)
    r.save(str(tmp))
    np.savez(tmp / REFERENCE_FILE, **lists)
    with open(tmp / "corpus.json", "w") as f:
        json.dump(dataclasses.asdict(s), f)
    try:
        os.replace(tmp, path)
    except OSError:  # another process saved the same corpus first
        shutil.rmtree(tmp, ignore_errors=True)
    # write the saved index out now, in set-up: left to the kernel, the
    # write-back of its gigabytes slows the host all through the window
    os.sync()
    return r, True
