"""The search path compiled for a described TPU v5e, at real widths.

Nothing runs: each test lowers and compiles one main-path program with the
TPU compiler against a ``v5e:2x2`` topology that is described, not attached.
A kernel Mosaic refuses (an unsupported gather, a misaligned window, more
VMEM than a kernel may use) fails here at no chip time.  The Pallas
programs must contain ``tpu_custom_call``, i.e. compiled kernels and not
the interpreter.

Widths: d = 128, nbits = 2 (32 packed bytes per token), nq = 32 query
tokens, L = doc_maxlen = 128, K = 65,536 centroids; the pipeline compiles
at 1M passages / 64M tokens under the paper's k = 10 parameters.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import pipeline, plaid
from repro.core.index import PlaidIndex
from repro.kernels import ops as K
from repro.retrieval import params_for_k
from repro.retrieval.backends import to_engine_params

KC, NQ, D, L, NBITS = 65536, 32, 128, 128, 2
PD = D * NBITS // 8
N_DOCS, N_TOK = 1 << 20, 1 << 26


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 - any failure means "no TPU here"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one; keep the cache out of it."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    cc.reset_cache()


@pytest.fixture
def S(one_chip, no_persistent_cache):
    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    return spec


def _compile_text(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("B", [1, 8])
def test_centroid_interaction_compiles(S, B):
    nd = 8192  # the default candidate cap
    txt = _compile_text(
        lambda s, c, m, k: K.centroid_interaction_batched(
            s, c, m, k, interpret=False
        ),
        S((B, KC, NQ), jnp.float32),
        S((B, nd, L), jnp.int32),
        S((B, NQ), jnp.float32),
        S((B, KC), jnp.bool_),
    )
    assert "tpu_custom_call" in txt


@pytest.mark.parametrize("B", [1, 8])
def test_decompress_and_score_compiles(S, B):
    n3 = 64  # max(ndocs // 4, k) at the k = 10 parameters
    txt = _compile_text(
        lambda *a: K.decompress_and_score_batched(
            *a, nbits=NBITS, interpret=False
        ),
        S((B, NQ, D), jnp.float32),
        S((B, NQ), jnp.float32),
        S((B, n3, L), jnp.int32),
        S((B, n3, L, PD), jnp.uint8),
        S((B, n3, L), jnp.bool_),
        S((KC, D), jnp.float32),
        S((2**NBITS,), jnp.float32),
    )
    assert "tpu_custom_call" in txt


@pytest.mark.parametrize("B", [1, 8])
def test_gather_decompress_maxsim_compiles(S, B):
    txt = _compile_text(
        lambda *a: K.gather_decompress_maxsim(
            *a, nbits=NBITS, doc_maxlen=L, interpret=False
        ),
        S((B, NQ, D), jnp.float32),
        S((B, NQ), jnp.float32),
        S((B, 64), jnp.int32),
        S((N_TOK,), jnp.int32),
        S((N_TOK, PD), jnp.uint8),
        S((N_DOCS + 1,), jnp.int32),
        S((N_DOCS,), jnp.int32),
        S((KC, D), jnp.float32),
        S((2**NBITS,), jnp.float32),
    )
    assert "tpu_custom_call" in txt


@pytest.mark.parametrize("B", [1, 8])
def test_decompress_residuals_compiles(S, B):
    txt = _compile_text(
        lambda p, w: K.decompress_residuals(
            p, w, nbits=NBITS, interpret=False
        ),
        S((B * 64 * L, PD), jnp.uint8),
        S((2**NBITS,), jnp.float32),
    )
    assert "tpu_custom_call" in txt


def _index_spec(S, ivf_list_cap=512) -> PlaidIndex:
    nnz = N_TOK // 3  # unique (centroid, passage) pairs
    i32, f32 = jnp.int32, jnp.float32
    return PlaidIndex(
        centroids=S((KC, D), f32),
        centroids_q=S((KC, D), jnp.int8),
        centroids_scale=S((KC,), f32),
        codes=S((N_TOK,), i32),
        residuals=S((N_TOK, PD), jnp.uint8),
        tok_pid=S((N_TOK,), i32),
        doc_offsets=S((N_DOCS + 1,), i32),
        doc_lens=S((N_DOCS,), i32),
        ivf_pids=S((nnz,), i32),
        ivf_offsets=S((KC + 1,), i32),
        ivf_lens=S((KC,), i32),
        eivf_eids=S((N_TOK,), i32),
        eivf_offsets=S((KC + 1,), i32),
        eivf_lens=S((KC,), i32),
        cutoffs=S((2**NBITS - 1,), f32),
        weights=S((2**NBITS,), f32),
        dim=D,
        nbits=NBITS,
        doc_maxlen=L,
        ivf_list_cap=ivf_list_cap,
        eivf_list_cap=2048,
    )


@pytest.mark.parametrize("impl", ["ref", "pallas"])
def test_run_pipeline_compiles_at_1m_passages(S, impl):
    """The whole batched search program (``backend="plaid"`` /
    ``"plaid-pallas"``) at B = 8 over a 1M-passage index: it compiles, and
    its arguments plus temporaries fit one chip's 16 GB."""
    B = 8
    params = plaid.clamp_params(
        to_engine_params(params_for_k(10), impl), N_DOCS
    )
    compiled = pipeline.run_pipeline_jit.lower(
        _index_spec(S),
        S((B, NQ, D), jnp.float32),
        S((B, NQ), jnp.float32),
        S((), jnp.float32),
        params=params,
        interpret=False,
    ).compile()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 16e9
    assert ("tpu_custom_call" in compiled.as_text()) == (impl == "pallas")
