"""The pipeline's stage scopes (``pipeline.STAGES``).

The scopes must name every stage's work and change nothing else: the
optimized program with its metadata stripped is the program compiled
without them.  A device trace carries each operation's ``op_name``, so a
reader sums the trace's operations by stage
(``plaidbench/tests/test_plaidbench_stages.py``).
"""
import contextlib
import functools
import re

import jax
import jax.numpy as jnp
import pytest

from repro import retrieval
from repro.core import pipeline, plaid
from repro.core.index import PlaidIndex
from repro.retrieval.backends import to_engine_params

# the harness's rehearsal cell (plaidbench/tests/data/rehearsal.k10.json)
K, D, L, NQ, NBITS, B = 1024, 128, 128, 32, 2, 4
N_DOCS, N_TOK = 3000, 3000 * 64
PD = D * NBITS // 8
STATIC = ("params", "diag", "funnel", "interpret")
#: opcodes that run nothing: not expected to carry a stage
_SKIP = ("parameter", "constant", "tuple", "bitcast")


def _index_spec() -> PlaidIndex:
    S = jax.ShapeDtypeStruct
    i32, f32 = jnp.int32, jnp.float32
    return PlaidIndex(
        centroids=S((K, D), f32),
        centroids_q=S((K, D), jnp.int8),
        centroids_scale=S((K,), f32),
        codes=S((N_TOK,), i32),
        residuals=S((N_TOK, PD), jnp.uint8),
        tok_pid=S((N_TOK,), i32),
        doc_offsets=S((N_DOCS + 1,), i32),
        doc_lens=S((N_DOCS,), i32),
        ivf_pids=S((N_TOK // 3,), i32),
        ivf_offsets=S((K + 1,), i32),
        ivf_lens=S((K,), i32),
        eivf_eids=S((N_TOK,), i32),
        eivf_offsets=S((K + 1,), i32),
        eivf_lens=S((K,), i32),
        cutoffs=S((2**NBITS - 1,), f32),
        weights=S((2**NBITS,), f32),
        dim=D,
        nbits=NBITS,
        doc_maxlen=L,
        ivf_list_cap=64,
        eivf_list_cap=256,
    )


def _compiled_text(impl: str) -> str:
    """The rehearsal-size search program, compiled by a fresh jit (so the
    scopes in force now are the ones traced)."""
    params = plaid.clamp_params(
        to_engine_params(
            retrieval.SearchParams(k=10, nprobe=1, t_cs=0.5, ndocs=128,
                                   candidate_cap=1024),
            impl,
        ),
        N_DOCS,
    )
    fn = jax.jit(functools.partial(pipeline.run_pipeline_impl),
                 static_argnames=STATIC)
    S = jax.ShapeDtypeStruct
    return fn.lower(
        _index_spec(), S((B, NQ, D), jnp.float32), S((B, NQ), jnp.float32),
        S((), jnp.float32), params=params,
    ).compile().as_text()


def _strip(hlo: str) -> str:
    """The module without metadata or its source tables."""
    body = hlo.split("\nFileNames")[0] if "\nFileNames" in hlo else hlo
    body = re.sub(r", metadata=\{[^}]*\}", "", body)
    return re.sub(r"stack_frame_id=\d+", "", body)


@pytest.mark.parametrize("impl", ["ref", "pallas"])
def test_scopes_change_nothing_but_metadata(impl, monkeypatch):
    scoped = _compiled_text(impl)
    monkeypatch.setattr(jax, "named_scope", lambda name: contextlib.nullcontext())
    plain = _compiled_text(impl)
    scope = re.compile(r'op_name="[^"]*/plaid\.')
    assert scope.search(scoped) and not scope.search(plain)
    assert _strip(scoped) == _strip(plain)


#: an entry-computation instruction: ``%name = <shape> <opcode>(...)``
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%(\S+)\s+=\s+(.*)$")
_OPCODE = re.compile(r"\s([a-z][\w\-]*)\(")
_OP_NAME = re.compile(r'op_name="([^"]*)"')


@pytest.mark.parametrize("impl", ["ref", "pallas"])
def test_stage_scopes_cover_the_program(impl):
    """At least 90 % of the entry instructions that run an operation the
    program wrote carry a ``plaid.*`` scope, and every stage has some.
    Instructions the compiler wrote (no ``op_name`` under the jit: layout
    copies, decomposed reduce-windows) are left out; a trace's reader gives
    them their neighbour's stage."""
    text = _compiled_text(impl)
    entry = text[text.index("\nENTRY"):].split("\n}\n")[0]
    paths = {}
    for line in entry.splitlines()[1:]:
        m = _INSTR.match(line)
        if m is None:
            continue
        op = _OPCODE.search(m.group(2))
        name = _OP_NAME.search(m.group(2))
        if op and op.group(1) not in _SKIP and name and "/" in name.group(1):
            paths[m.group(1)] = name.group(1)
    stage = {n: [p for p in path.split("/") if p.startswith("plaid.")]
             for n, path in paths.items()}
    staged = [n for n in paths if stage[n]]
    assert len(staged) >= 0.9 * len(paths), [paths[n] for n in paths if not stage[n]]
    assert {stage[n][-1] for n in staged} == set(pipeline.STAGES)
