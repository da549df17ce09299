"""Synthetic data generators for every arch family (offline-friendly).

Retrieval corpora are generated with CLUSTER STRUCTURE (topic centers +
within-topic noise, unit-normalized) so k-means centroids are meaningful and
PLAID's centroid interaction behaves as it does on real embeddings; queries
are derived from documents with noise so relevance is well-defined (the
source doc is the gold passage).
"""
from __future__ import annotations

import numpy as np


# --------------------------------------------------------------------------
# Retrieval (PLAID / ColBERT)
# --------------------------------------------------------------------------
def embedding_corpus(
    n_docs: int,
    dim: int = 128,
    *,
    min_len: int = 8,
    max_len: int = 48,
    n_topics: int = 32,
    n_concepts: int | None = None,
    noise: float = 0.35,
    seed: int = 0,
):
    """Concept-vocabulary corpus matching late-interaction geometry.

    Tokens cluster around unit "concept" vectors (the structure ColBERTv2's
    k-means centroids capture); a document is a bag of concepts drawn from
    its topic's concept pool; ``noise`` is the RELATIVE perturbation norm
    (token = normalize(concept + noise * u), ||u|| ~ 1).  Query tokens (below)
    then score ~1/sqrt(1+noise^2) against their own concept and ~0 against
    the rest — the skewed centroid-score distribution of the paper's Fig. 4,
    which makes the t_cs pruning thresholds meaningful.

    Returns (list of (len_i, dim) unit-norm arrays, doc topic ids).
    """
    rng = np.random.default_rng(seed)
    if n_concepts is None:
        n_concepts = int(min(4096, max(64, n_docs)))
    concepts = rng.standard_normal((n_concepts, dim)).astype(np.float32)
    concepts /= np.linalg.norm(concepts, axis=-1, keepdims=True)
    concept_topic = np.arange(n_concepts) % n_topics
    pools = [np.where(concept_topic == t)[0] for t in range(n_topics)]
    doc_topics = rng.integers(0, n_topics, n_docs)
    nscale = noise / np.sqrt(dim)
    docs = []
    for t in doc_topics:
        ln = int(rng.integers(min_len, max_len + 1))
        cids = rng.choice(pools[t], ln)
        e = concepts[cids] + nscale * rng.standard_normal((ln, dim)).astype(
            np.float32
        )
        e /= np.linalg.norm(e, axis=-1, keepdims=True)
        docs.append(e.astype(np.float32))
    return docs, doc_topics


def queries_from_docs(
    docs: list[np.ndarray],
    n_queries: int,
    *,
    q_len: int = 8,
    noise: float = 0.12,
    seed: int = 1,
):
    """Queries = noisy subsets of doc tokens; gold pid = source doc."""
    rng = np.random.default_rng(seed)
    pids = rng.integers(0, len(docs), n_queries)
    qs, golds = [], []
    dim = docs[0].shape[1]
    nscale = noise / np.sqrt(dim)  # relative perturbation (see above)
    for pid in pids:
        d = docs[pid]
        idx = rng.integers(0, len(d), q_len)
        q = d[idx] + nscale * rng.standard_normal((q_len, dim)).astype(
            np.float32
        )
        q /= np.linalg.norm(q, axis=-1, keepdims=True)
        qs.append(q.astype(np.float32))
        golds.append(int(pid))
    return np.stack(qs), np.asarray(golds)


class CorpusStream:
    """Corpus-scale version of :func:`embedding_corpus`, generated in chunks.

    The same concept geometry, with three differences that matter at the
    scale of a deployment (1M+ passages, where the corpus is 32+ GB of f32
    and never sits whole on the host):

    * heavy-tailed passage lengths: log-normal with mean ``mean_len``
      (MS MARCO's 68), clipped to ``[min_len, max_len]``;
    * each passage draws ``ceil(len / repeat)`` concepts and repeats them,
      so an IVF list holds about ``repeat`` tokens per passage;
    * tokens are made on the device: a chunk's payload is its (T,) concept
      ids plus the chunk index, and :attr:`encode` maps it to (T, dim)
      unit-norm embeddings — the ``encode_fn`` of a
      ``repro.build.chunks.ChunkStream``.

    Every chunk holds exactly ``chunk_tokens`` tokens and ends on a passage
    boundary (its last passage takes the remainder), so the build compiles
    one program; chunks are added until at least ``n_docs`` passages exist.
    Chunk ``c`` is a pure function of ``(seed, c)``.
    """

    def __init__(
        self,
        n_docs: int,
        dim: int = 128,
        *,
        mean_len: float = 68.0,
        min_len: int = 8,
        max_len: int = 128,
        n_topics: int = 32,
        n_concepts: int = 1 << 16,
        repeat: float = 3.0,
        noise: float = 0.35,
        chunk_tokens: int = 1 << 18,
        seed: int = 0,
    ):
        import functools

        import jax

        self.dim, self.seed = dim, seed
        self.min_len, self.max_len, self.mean_len = min_len, max_len, mean_len
        self.n_topics, self.n_concepts, self.repeat = n_topics, n_concepts, repeat
        self.chunk_tokens = chunk_tokens
        self.chunk_lens, n = [], 0
        while n < max(1, n_docs):
            self.chunk_lens.append(self._lens(len(self.chunk_lens)))
            n += len(self.chunk_lens[-1])
        n_chunks = len(self.chunk_lens)
        self.chunk_pid0 = np.cumsum([0] + [len(x) for x in self.chunk_lens])
        self.n_docs = int(self.chunk_pid0[-1])
        self.n_tokens = n_chunks * chunk_tokens
        self.encode = jax.jit(
            functools.partial(
                _corpus_tokens, seed=seed, n_concepts=n_concepts, dim=dim,
                noise=noise,
            )
        )

    def _rng(self, c: int, stream: int):
        return np.random.default_rng((self.seed, c, stream))

    def _lens(self, c: int) -> np.ndarray:
        rng = self._rng(c, 0)
        sigma = 0.6
        mu = np.log(self.mean_len) - sigma**2 / 2
        draw = np.clip(
            np.rint(rng.lognormal(mu, sigma, 2 * self.chunk_tokens // self.min_len)),
            self.min_len, self.max_len,
        ).astype(np.int64)
        ends = np.cumsum(draw)
        n = int(np.searchsorted(ends, self.chunk_tokens, side="right"))
        lens = draw[:n]
        rest = self.chunk_tokens - int(lens.sum())
        return np.append(lens, rest).astype(np.int32) if rest else lens.astype(np.int32)

    def payload(self, c: int) -> np.ndarray:
        """Chunk ``c``'s (T + 1,) int32 payload: concept ids, chunk index."""
        lens = self.chunk_lens[c]
        rng = self._rng(c, 1)
        nd = len(lens)
        pool = self.n_concepts // self.n_topics
        topics = rng.integers(0, self.n_topics, nd)
        m = np.ceil(lens / self.repeat).astype(np.int64)
        slots = rng.integers(0, pool, (nd, int(m.max())))
        tok_doc = np.repeat(np.arange(nd), lens)
        r = (rng.random(tok_doc.shape[0]) * m[tok_doc]).astype(np.int64)
        ids = topics[tok_doc] * pool + slots[tok_doc, r]
        return np.append(ids, c).astype(np.int32)

    def chunks(self):
        """Fresh iterator of ``(payload, doc_lens)`` chunks (re-iterable)."""
        for c, lens in enumerate(self.chunk_lens):
            yield self.payload(c), lens

    def docs(self, c: int) -> list[np.ndarray]:
        """Chunk ``c``'s passages as host (len_i, dim) arrays; passage ``j``
        of the list has global pid ``chunk_pid0[c] + j``."""
        emb = np.asarray(self.encode(self.payload(c)))
        return np.split(emb, np.cumsum(self.chunk_lens[c])[:-1])


def _corpus_tokens(payload, *, seed, n_concepts, dim, noise):
    """(T + 1,) payload -> (T, dim) unit-norm token embeddings (device)."""
    import jax
    import jax.numpy as jnp

    k_c, k_n = jax.random.split(jax.random.PRNGKey(seed))
    concepts = jax.random.normal(k_c, (n_concepts, dim), jnp.float32)
    concepts = concepts / jnp.linalg.norm(concepts, axis=-1, keepdims=True)
    ids = payload[:-1]
    u = jax.random.normal(
        jax.random.fold_in(k_n, payload[-1]), (ids.shape[0], dim), jnp.float32
    )
    e = concepts[ids] + (noise / np.sqrt(dim)) * u
    return e / jnp.linalg.norm(e, axis=-1, keepdims=True)


# --------------------------------------------------------------------------
# LM token streams (zipfian synthetic corpus)
# --------------------------------------------------------------------------
def lm_batches(vocab: int, batch: int, seq: int, *, seed: int = 0):
    """Infinite iterator of {tokens, targets} with zipfian marginals."""
    rng = np.random.default_rng(seed)
    ranks = np.arange(1, vocab + 1)
    probs = 1.0 / ranks
    probs /= probs.sum()
    while True:
        t = rng.choice(vocab, size=(batch, seq + 1), p=probs).astype(np.int32)
        yield {"tokens": t[:, :-1], "targets": t[:, 1:]}


def colbert_batches(
    vocab: int,
    batch: int,
    *,
    q_len: int = 32,
    d_len: int = 64,
    nway: int = 4,
    seed: int = 0,
):
    """Training triples for the ColBERT loss: positives share tokens with
    the query (lexical overlap => learnable relevance signal)."""
    rng = np.random.default_rng(seed)
    while True:
        q = rng.integers(0, vocab, (batch, q_len)).astype(np.int32)
        d = rng.integers(0, vocab, (batch, nway, d_len)).astype(np.int32)
        # positive (slot 0) copies query tokens into a random span
        start = rng.integers(0, d_len - q_len, batch)
        for i in range(batch):
            d[i, 0, start[i] : start[i] + q_len] = q[i]
        yield {
            "q_tokens": q,
            "q_mask": np.ones((batch, q_len), np.float32),
            "d_tokens": d,
            "d_mask": np.ones((batch, nway, d_len), np.float32),
            "target_scores": np.concatenate(
                [
                    np.full((batch, 1), 4.0, np.float32),
                    np.zeros((batch, nway - 1), np.float32),
                ],
                axis=1,
            ),
        }


# --------------------------------------------------------------------------
# RecSys batches
# --------------------------------------------------------------------------
def recsys_batches(cfg, batch: int, *, seed: int = 0):
    rng = np.random.default_rng(seed)
    while True:
        out = {"labels": rng.integers(0, 2, batch).astype(np.int32)}
        if cfg.interaction in ("cin", "concat"):
            out["sparse_ids"] = rng.integers(
                0, cfg.hash_size, (batch, cfg.n_sparse)
            ).astype(np.int32)
            out["dense_feats"] = rng.standard_normal(
                (batch, cfg.n_dense)
            ).astype(np.float32)
        if cfg.seq_len:
            out["seq_ids"] = rng.integers(
                0, cfg.item_vocab, (batch, cfg.seq_len)
            ).astype(np.int32)
            out["target_id"] = rng.integers(0, cfg.item_vocab, batch).astype(
                np.int32
            )
            if cfg.n_dense:
                out["dense_feats"] = rng.standard_normal(
                    (batch, cfg.n_dense)
                ).astype(np.float32)
        if cfg.interaction == "bidir-seq":
            mask = rng.random((batch, cfg.seq_len)) < cfg.mask_frac
            labels = np.where(mask, out["seq_ids"], -1).astype(np.int32)
            seq = out["seq_ids"].copy()
            seq[mask] = cfg.item_vocab  # [MASK] token row
            out["seq_ids"], out["labels"] = seq, labels
        yield out
