"""Pallas kernels vs pure-jnp oracles: shape/dtype/nbits sweeps (interpret
mode on CPU; the same kernels lower through Mosaic on TPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import residual_codec as rc
from repro.kernels import decompress as kdec
from repro.kernels import dispatch as kdisp
from repro.kernels import fused_score as kfs
from repro.kernels import ops as K
from repro.kernels import ref as R


@pytest.mark.parametrize("nd,L,Kc,nq", [(5, 7, 16, 4), (32, 12, 64, 8), (70, 20, 128, 32)])
def test_centroid_interaction_matches_ref(nd, L, Kc, nq):
    rng = np.random.default_rng(0)
    s_cq = jnp.asarray(rng.standard_normal((Kc, nq)), jnp.float32)
    codes = rng.integers(-1, Kc, (nd, L)).astype(np.int32)
    keep = jnp.asarray(rng.random(Kc) > 0.3)
    q_mask = jnp.asarray((rng.random(nq) > 0.1).astype(np.float32))
    got = K.centroid_interaction(
        s_cq, jnp.asarray(codes), q_mask, keep, interpret=True, doc_block=16
    )
    want = R.centroid_interaction_ref(s_cq, jnp.asarray(codes), keep, q_mask)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("nbits", [1, 2, 4])
@pytest.mark.parametrize("n,dim", [(16, 16), (100, 128)])
def test_decompress_matches_ref(nbits, n, dim):
    rng = np.random.default_rng(1)
    packed = rng.integers(0, 256, (n, dim * nbits // 8)).astype(np.uint8)
    weights = jnp.asarray(np.sort(rng.standard_normal(2**nbits)), jnp.float32)
    got = K.decompress_residuals(
        jnp.asarray(packed), weights, nbits=nbits, interpret=True, row_block=32
    )
    want = R.decompress_residuals_ref(jnp.asarray(packed), weights, nbits=nbits)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-6)


@pytest.mark.parametrize("nbits", [1, 2])
@pytest.mark.parametrize("nd,L,nq", [(6, 5, 4), (20, 11, 16)])
def test_fused_decompress_score_matches_ref(nbits, nd, L, nq):
    rng = np.random.default_rng(2)
    dim, Kc = 32, 16
    q = jnp.asarray(rng.standard_normal((nq, dim)), jnp.float32)
    q_mask = jnp.ones((nq,), jnp.float32)
    codes = rng.integers(-1, Kc, (nd, L)).astype(np.int32)
    packed = rng.integers(0, 256, (nd, L, dim * nbits // 8)).astype(np.uint8)
    tok_valid = codes >= 0
    cents = jnp.asarray(rng.standard_normal((Kc, dim)), jnp.float32)
    weights = jnp.asarray(np.sort(rng.standard_normal(2**nbits)), jnp.float32)
    got = K.decompress_and_score(
        q, q_mask, jnp.asarray(codes), jnp.asarray(packed),
        jnp.asarray(tok_valid), cents, weights, nbits=nbits,
        interpret=True, doc_block=4,
    )
    want = R.decompress_and_score_ref(
        q, q_mask, jnp.asarray(codes), jnp.asarray(packed),
        jnp.asarray(tok_valid), cents, weights, nbits=nbits,
    )
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5)


def test_engine_pallas_impl_matches_ref_impl():
    from repro.core import index as index_mod, plaid
    from repro.data import synthetic as syn

    docs, _ = syn.embedding_corpus(150, dim=32, seed=3)
    idx = index_mod.build_index(docs, num_centroids=32, nbits=2, kmeans_iters=3)
    qs, _ = syn.queries_from_docs(docs, 8)
    ref = plaid.PlaidEngine(idx, plaid.params_for_k(10, impl="ref"))
    pal = plaid.PlaidEngine(idx, plaid.params_for_k(10, impl="pallas"))
    s1, p1 = ref.search_batch(jnp.asarray(qs))
    s2, p2 = pal.search_batch(jnp.asarray(qs))
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s2), rtol=1e-4, atol=1e-4)
    np.testing.assert_array_equal(np.asarray(p1), np.asarray(p2))


def test_unpack_matches_numpy_bit_semantics():
    """MSB-first packing: byte 0b10_01_00_11 with nbits=2 -> [2,1,0,3]."""
    packed = jnp.asarray([[0b10010011]], jnp.uint8)
    out = rc.unpack_indices(packed, 2)
    np.testing.assert_array_equal(np.asarray(out)[0], [2, 1, 0, 3])


# --------------------------------------------------------------------------
# fused gather -> decompress -> maxsim megakernel vs its jnp oracle
# --------------------------------------------------------------------------
def _csr_corpus(rng, n_docs, max_len, Kc, dim, nbits):
    """Raw CSR token arrays, no index build: ragged lens, packed residuals."""
    lens = rng.integers(1, max_len + 1, n_docs).astype(np.int32)
    offs = np.zeros(n_docs + 1, np.int32)
    offs[1:] = np.cumsum(lens)
    nt = int(offs[-1])
    codes = rng.integers(0, Kc, nt).astype(np.int32)
    packed = rng.integers(0, 256, (nt, dim * nbits // 8)).astype(np.uint8)
    cents = rng.standard_normal((Kc, dim)).astype(np.float32)
    weights = np.sort(rng.standard_normal(2**nbits)).astype(np.float32)
    return lens, offs, codes, packed, cents, weights


@pytest.mark.parametrize("nbits", [1, 2, 4])
@pytest.mark.parametrize("B,n3,nq", [(1, 4, 3), (3, 7, 8)])
def test_gather_decompress_maxsim_matches_ref(nbits, B, n3, nq):
    """The megakernel (interpret) == the jnp oracle, including -1 pad lanes
    and clamped windows for passages at the very end of the token array."""
    rng = np.random.default_rng(7)
    n_docs, max_len, Kc, dim = 12, 9, 16, 32
    lens, offs, codes, packed, cents, weights = _csr_corpus(
        rng, n_docs, max_len, Kc, dim, nbits
    )
    pids = rng.integers(0, n_docs, (B, n3)).astype(np.int32)
    pids[:, 0] = n_docs - 1  # window clamp: last passage in the CSR array
    pids[-1, -2:] = -1  # pad lanes
    args = (
        jnp.asarray(rng.standard_normal((B, nq, dim)), jnp.float32),
        jnp.asarray((rng.random((B, nq)) > 0.2).astype(np.float32)),
        jnp.asarray(pids),
        jnp.asarray(codes),
        jnp.asarray(packed),
        jnp.asarray(offs),
        jnp.asarray(lens),
        jnp.asarray(cents),
        jnp.asarray(weights),
    )
    got = K.gather_decompress_maxsim(
        *args, nbits=nbits, doc_maxlen=max_len, interpret=True
    )
    want = R.gather_decompress_maxsim_ref(
        *args, nbits=nbits, doc_maxlen=max_len
    )
    # pid == -1 lanes are pinned by the caller in both real paths
    got = jnp.where(args[2] >= 0, got, 0.0)
    want = jnp.where(args[2] >= 0, want, 0.0)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5
    )


def test_gather_decompress_maxsim_tiny_corpus():
    """Total token count < doc_maxlen: the kernel's fixed-size window pads
    the token arrays instead of reading out of range."""
    rng = np.random.default_rng(8)
    lens, offs, codes, packed, cents, weights = _csr_corpus(
        rng, n_docs=3, max_len=2, Kc=8, dim=16, nbits=2
    )
    assert int(offs[-1]) < 8  # smaller than the doc_maxlen below
    pids = np.asarray([[0, 2, -1]], np.int32)
    args = (
        jnp.asarray(rng.standard_normal((1, 4, 16)), jnp.float32),
        jnp.ones((1, 4), jnp.float32),
        jnp.asarray(pids),
        jnp.asarray(codes),
        jnp.asarray(packed),
        jnp.asarray(offs),
        jnp.asarray(lens),
        jnp.asarray(cents),
        jnp.asarray(weights),
    )
    got = K.gather_decompress_maxsim(
        *args, nbits=2, doc_maxlen=8, interpret=True
    )
    want = R.gather_decompress_maxsim_ref(*args, nbits=2, doc_maxlen=8)
    got = jnp.where(args[2] >= 0, got, 0.0)
    want = jnp.where(args[2] >= 0, want, 0.0)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5
    )


# --------------------------------------------------------------------------
# dispatch: one cached backend resolution + REPRO_FORCE_INTERPRET override
# --------------------------------------------------------------------------
@pytest.fixture
def fresh_dispatch():
    """Reset the process-wide resolution cache around the test (the suite
    must go back to resolving from the real backend afterwards)."""
    kdisp._reset_cache()
    yield
    kdisp._reset_cache()


def test_dispatch_resolves_backend_once(fresh_dispatch, monkeypatch):
    calls = []
    real = kdisp.jax.default_backend
    monkeypatch.setattr(
        kdisp.jax, "default_backend",
        lambda: calls.append(1) or real(),
    )
    first = kdisp.default_interpret()
    for _ in range(5):
        assert kdisp.default_interpret() is first
        assert kdisp.resolve_interpret(None) is first
    assert len(calls) == 1  # consulted once per process, not per launch


@pytest.mark.parametrize(
    "raw,want",
    [("1", True), ("true", True), (" YES ", True), ("on", True),
     ("0", False), ("false", False), ("No", False), ("off", False)],
)
def test_dispatch_env_override(fresh_dispatch, monkeypatch, raw, want):
    monkeypatch.setenv("REPRO_FORCE_INTERPRET", raw)
    assert kdisp.default_interpret() is want
    assert kdisp.resolve_interpret(None) is want
    # an explicit bool still beats the env override
    assert kdisp.resolve_interpret(not want) is (not want)


def test_dispatch_env_override_rejects_garbage(fresh_dispatch, monkeypatch):
    monkeypatch.setenv("REPRO_FORCE_INTERPRET", "maybe")
    with pytest.raises(ValueError, match="REPRO_FORCE_INTERPRET"):
        kdisp.default_interpret()


def test_dispatch_cache_pins_env_at_first_resolution(
    fresh_dispatch, monkeypatch
):
    """The env var is read at FIRST resolution only — flipping it later
    without _reset_cache() changes nothing (documented cache semantics)."""
    monkeypatch.setenv("REPRO_FORCE_INTERPRET", "0")
    assert kdisp.default_interpret() is False
    monkeypatch.setenv("REPRO_FORCE_INTERPRET", "1")
    assert kdisp.default_interpret() is False
    kdisp._reset_cache()
    assert kdisp.default_interpret() is True


# --------------------------------------------------------------------------
# pack <-> unpack round trip, shared between codec and kernels
# --------------------------------------------------------------------------
def test_unpack_shared_single_source():
    """The fused tail scores through the decompress module's stage-4 kernel
    — the SAME function object, so bit semantics cannot drift between the
    two."""
    assert (
        kfs.decompress_and_score_batched_pallas
        is kdec.decompress_and_score_batched_pallas
    )


def _roundtrip(indices, nbits):
    """Pack with the codec, unpack with BOTH the codec and the kernels'
    shared shift/mask chain; all three must agree."""
    packed = rc.pack_indices(jnp.asarray(indices, jnp.uint8), nbits)
    via_codec = np.asarray(rc.unpack_indices(packed, nbits))
    via_kernel = np.asarray(kdec._unpack(packed.astype(jnp.int32), nbits))
    np.testing.assert_array_equal(via_codec, indices)
    np.testing.assert_array_equal(via_kernel, indices)


@pytest.mark.parametrize("nbits", [1, 2, 4, 8])
@pytest.mark.parametrize("lead", [(), (1,), (3, 5), (7, 1, 3)])
def test_pack_unpack_roundtrip(nbits, lead):
    """Deterministic round-trip sweep: odd leading shapes, dim an odd
    multiple of values-per-byte (the tail byte is partially 'ragged' in
    value terms but still a whole byte, per the codec's contract)."""
    vpb = 8 // nbits
    dim = vpb * 7  # odd multiple: not a power-of-two lane count
    rng = np.random.default_rng(nbits)
    indices = rng.integers(0, 2**nbits, (*lead, dim)).astype(np.uint8)
    _roundtrip(indices, nbits)


def test_pack_rejects_ragged_dim():
    with pytest.raises(ValueError, match="not divisible"):
        rc.pack_indices(jnp.zeros((4, 3), jnp.uint8), 2)  # vpb=4, 3 % 4 != 0


try:
    from hypothesis import given, settings
    from hypothesis import strategies as st

    @settings(max_examples=50, deadline=None)
    @given(data=st.data(), nbits=st.sampled_from([1, 2, 4, 8]))
    def test_pack_unpack_roundtrip_property(data, nbits):
        """Property form of the round trip (runs in CI where hypothesis is
        installed; skipped cleanly where it isn't)."""
        vpb = 8 // nbits
        n_bytes = data.draw(st.integers(1, 9), label="bytes_per_row")
        lead = data.draw(
            st.lists(st.integers(1, 4), min_size=0, max_size=2), label="lead"
        )
        shape = (*lead, n_bytes * vpb)
        flat = data.draw(
            st.lists(
                st.integers(0, 2**nbits - 1),
                min_size=int(np.prod(shape)),
                max_size=int(np.prod(shape)),
            ),
            label="values",
        )
        indices = np.asarray(flat, np.uint8).reshape(shape)
        _roundtrip(indices, nbits)

except ImportError:  # pragma: no cover - exercised only without hypothesis

    @pytest.mark.skip(reason="hypothesis not installed")
    def test_pack_unpack_roundtrip_property():
        pass
