"""Data pipeline: samplers, corpora, batch generators."""
import numpy as np
import pytest
try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # container images without hypothesis: skip only the
    # property-based tests; the rest of the module still runs
    import pytest as _pytest

    def given(*_a, **_k):
        return lambda f: _pytest.mark.skip(reason="hypothesis not installed")(f)

    def settings(*_a, **_k):
        return lambda f: f

    class _AnyStrategy:
        def __getattr__(self, _name):
            return lambda *a, **k: None

    st = _AnyStrategy()

from repro.data import graphs as G
from repro.data import synthetic as syn


def test_corpus_unit_norm_and_clustered():
    docs, topics = syn.embedding_corpus(50, dim=16, n_topics=4, seed=0)
    for d in docs[:5]:
        np.testing.assert_allclose(np.linalg.norm(d, axis=-1), 1.0, rtol=1e-5)
    # same-topic docs are more similar than cross-topic
    means = np.stack([d.mean(0) for d in docs])
    same = [
        means[i] @ means[j]
        for i in range(20)
        for j in range(20)
        if i < j and topics[i] == topics[j]
    ]
    diff = [
        means[i] @ means[j]
        for i in range(20)
        for j in range(20)
        if i < j and topics[i] != topics[j]
    ]
    assert np.mean(same) > np.mean(diff)


def test_corpus_stream_chunks_are_exact_and_reproducible():
    cs = syn.CorpusStream(500, dim=16, n_concepts=256, chunk_tokens=2048,
                          seed=3)
    assert cs.n_docs >= 500
    assert cs.n_tokens == len(cs.chunk_lens) * 2048
    chunks = list(cs.chunks())
    assert len(chunks) == len(cs.chunk_lens)
    for payload, lens in chunks:
        assert lens.sum() == 2048 and lens.max() <= 128
        assert payload.shape == (2049,) and payload[:-1].max() < 256
    # chunk c is a pure function of (seed, c)
    again = syn.CorpusStream(500, dim=16, n_concepts=256, chunk_tokens=2048,
                             seed=3)
    np.testing.assert_array_equal(again.payload(1), chunks[1][0])
    docs = cs.docs(1)
    assert [len(d) for d in docs] == list(cs.chunk_lens[1])
    np.testing.assert_allclose(
        np.linalg.norm(np.concatenate(docs), axis=-1), 1.0, rtol=1e-5
    )
    lens = np.concatenate(cs.chunk_lens)
    assert 40 < lens.mean() < 80  # heavy-tailed around MS MARCO's 68


def test_queries_reference_their_gold_doc():
    docs, _ = syn.embedding_corpus(30, dim=16, seed=1)
    qs, gold = syn.queries_from_docs(docs, 10, q_len=4)
    assert qs.shape == (10, 4, 16)
    for q, g in zip(qs[:3], gold[:3]):
        sims = [float((q @ d.T).max(-1).sum()) for d in docs]
        assert int(np.argmax(sims)) == g


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 1000))
def test_neighbor_sample_invariants(seed):
    g = G.random_graph(200, 1500, d_feat=4, n_classes=3, seed=seed)
    blk = G.neighbor_sample(g, np.arange(8), (5, 3), seed=seed)
    n_real, e_real = blk["n_real_nodes"], blk["n_real_edges"]
    # seeds occupy the first slots
    np.testing.assert_array_equal(blk["nodes"][:8], np.arange(8))
    # masks consistent
    assert blk["node_mask"].sum() == n_real
    assert blk["edge_mask"].sum() == e_real
    # local indices stay in the real-node range
    assert blk["edge_src"][:e_real].max(initial=0) < n_real
    assert blk["edge_dst"][:e_real].max(initial=0) < n_real
    # every real edge's dst is reachable: dst must be a previously-seen node
    assert (blk["edge_dst"][:e_real] < n_real).all()
    # fanout bound: each hop adds at most fanout * frontier edges
    assert e_real <= 8 * 5 + 8 * 5 * 3


def test_molecule_batch_shapes():
    b = G.molecule_batch(4, 6, 10)
    assert b["z"].shape == (24,)
    assert b["edge_src"].shape == (40,)
    assert (b["edge_src"] // 6 == b["edge_dst"] // 6).all()  # within-molecule
    assert b["energy"].shape == (4,)


def test_lm_batches_zipfian():
    it = syn.lm_batches(100, 4, 32, seed=0)
    b = next(it)
    assert b["tokens"].shape == (4, 32)
    assert b["tokens"].max() < 100


def test_colbert_batches_positive_overlap():
    it = syn.colbert_batches(500, 4, q_len=6, d_len=20, nway=3)
    b = next(it)
    for i in range(4):
        q = set(b["q_tokens"][i].tolist())
        pos = set(b["d_tokens"][i, 0].tolist())
        neg = set(b["d_tokens"][i, 1].tolist())
        assert len(q & pos) >= len(q & neg)
