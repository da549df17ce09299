"""A corpus split over chips, on the CPU: the plain PLAID reference shard by
shard, the index cache's refusal of a save made for another configuration,
the payload written in place, the freeing of a sharded retriever's arrays,
and a cell whose ``shards`` and ``chips`` differ."""
from __future__ import annotations

import json
import os
import pathlib
import subprocess
import sys

import numpy as np
import pytest

from plaidbench import corpus as corpus_mod, reference, spec
from plaidbench.tests.rehearsal import CONFIG, DATA, SHARDED_CAP, make_root

REPO = pathlib.Path(__file__).resolve().parents[2]


def _config(**kw) -> dict:
    cfg = json.loads((DATA / f"{CONFIG}.json").read_text())
    cfg.update(kw)
    return cfg


@pytest.fixture(scope="module")
def rehearsal():
    """The rehearsal corpus (3,000 passages), its inverted lists and 8
    queries of one seed."""
    c = corpus_mod.Corpus(corpus_mod.CorpusSpec.from_config(_config()))
    _, _, _, lists = c.payload()
    qs, _ = c.queries(8, 5)
    return c, lists, qs


# ---- the split ---------------------------------------------------------------
def test_shards_split_contiguous_pid_ranges_as_the_program_does():
    r = reference.shard_ranges(8_841_823, 4)
    assert r == [(0, 2_210_456), (2_210_456, 4_420_912), (4_420_912, 6_631_368),
                 (6_631_368, 8_841_823)]
    assert reference.shard_ranges(10, 1) == [(0, 10)]
    search = {"candidate_cap": 8192}
    assert reference.shard_cap(search, 8_841_823, 4) == 8192
    assert reference.shard_cap(search, 3000, 4) == 750  # clamped to the shard's size
    assert reference.shard_cap(search, 1, 1) == 2
    assert spec.shards({}) == 1 and spec.shards({"shards": 4}) == 4
    for bad in (0, -1, 2.0, True, "4"):
        with pytest.raises(ValueError, match="shards"):
            spec.shards({"name": "x", "shards": bad})


# ---- the reference, shard by shard -------------------------------------------
def _one_shard_lists(lists: dict, lo: int, hi: int) -> dict:
    """The inverted lists with every code's list cut to the pids of
    ``[lo, hi)`` (global pids), written plainly, code by code."""
    cp, co = lists["code_pids"], lists["code_off"]
    kept = [cp[co[c] : co[c + 1]] for c in range(len(co) - 1)]
    kept = [x[(x >= lo) & (x < hi)] for x in kept]
    off = np.zeros(len(kept) + 1, np.int32)
    np.cumsum([len(x) for x in kept], out=off[1:])
    return dict(lists, code_pids=np.concatenate(kept).astype(np.int32), code_off=off)


def test_sharded_reference_is_each_shards_top_k_merged_by_score(rehearsal):
    c, lists, qs = rehearsal
    search = _config()["search"] | {"candidate_cap": SHARDED_CAP}
    s4, p4, reached4 = reference.plaid_topk(c, lists, qs, search, shards=4)
    per = -(-c.spec.passages // 4)
    parts = [
        reference.plaid_topk(c, _one_shard_lists(lists, lo, min(lo + per, c.spec.passages)),
                             qs, search | {"candidate_cap": min(SHARDED_CAP, per)})
        for lo in range(0, c.spec.passages, per)
    ]
    s = np.concatenate([x[0] for x in parts], axis=1)
    p = np.concatenate([x[1] for x in parts], axis=1)
    order = np.argsort(-s, axis=1, kind="stable")[:, : search["k"]]
    np.testing.assert_array_equal(p4, np.take_along_axis(p, order, axis=1))
    np.testing.assert_array_equal(s4, np.take_along_axis(s, order, axis=1))
    np.testing.assert_array_equal(reached4, np.max([x[2] for x in parts], axis=0))
    # the cap binds one index over the whole corpus and no shard: the
    # split changes the answers, and every shard contributes
    _, p1, reached1 = reference.plaid_topk(c, lists, qs, search)
    assert reached4.max() < SHARDED_CAP < reached1.min()
    assert not np.array_equal(p1, p4)
    assert set(p4[p4 >= 0] // per) == {0, 1, 2, 3}


#: ``plaid_topk`` at one shard on the rehearsal corpus, four queries of seed
#: 2**33 + 7: the outputs, bit for bit, of the one-index reference as it was
#: before it learned to split a corpus.
PINNED_PIDS = [
    [611, 995, 2706, 1562, 2039, 247, 1372, 1402, 1537, 95],
    [1569, 2714, 2482, 543, 1875, 1352, 225, 1090, 1244, 682],
    [888, 6, 542, 761, 2578, 79, 1309, 1279, 407, 2577],
    [350, 1491, 457, 312, 1875, 542, 507, 1499, 299, 445],
]
PINNED_SCORES = [
    [31.332759857177734, 22.670974731445312, 19.925771713256836, 19.386903762817383,
     18.962114334106445, 18.387325286865234, 18.333213806152344, 18.231231689453125,
     17.3093204498291, 17.254234313964844],
    [31.27419662475586, 19.128543853759766, 18.52547836303711, 17.41423797607422,
     17.079559326171875, 17.01064682006836, 16.501697540283203, 16.487445831298828,
     16.414844512939453, 16.184341430664062],
    [31.313190460205078, 18.68901252746582, 18.372005462646484, 16.807348251342773,
     16.183786392211914, 16.09652328491211, 15.647356986999512, 15.599347114562988,
     15.546021461486816, 15.4815092086792],
    [31.509004592895508, 26.739715576171875, 24.07823944091797, 24.068466186523438,
     21.05438232421875, 20.466190338134766, 20.45453643798828, 20.28426742553711,
     18.409122467041016, 18.156871795654297],
]
PINNED_REACHED = [309, 360, 361, 219]


def test_one_shard_gives_the_one_index_answers(rehearsal):
    c, lists, _ = rehearsal
    qs, _ = c.queries(4, 2**33 + 7)
    search = _config()["search"]
    for shards in ({}, {"shards": 1}):
        s, p, reached = reference.plaid_topk(c, lists, qs, search, **shards)
        np.testing.assert_array_equal(p, PINNED_PIDS)
        np.testing.assert_array_equal(s, PINNED_SCORES)
        np.testing.assert_array_equal(reached, PINNED_REACHED)


# ---- the payload, written in place -------------------------------------------
def _payload_by_concatenation(c):
    """The payload as it was made before it was written in place: each
    block's valid tokens appended to lists, then concatenated."""
    import functools

    import jax

    s = c.spec
    packed_fn = jax.jit(functools.partial(corpus_mod.pack, nbits=s.nbits))
    codes, packed = [], []
    for _, n, pids in c.blocks(np.arange(s.passages, dtype=np.int32)):
        ids, buckets, valid = c.payload_block(pids)
        ids, valid = np.asarray(ids)[:n], np.asarray(valid)[:n]
        codes.append(ids[valid])
        packed.append(np.asarray(packed_fn(buckets))[:n][valid])
    return np.concatenate(codes), np.concatenate(packed)


def _saved_arrays(path: pathlib.Path) -> dict:
    out = {}
    for f in sorted(path.rglob("*")):
        key = str(f.relative_to(path))
        if f.suffix == ".npy":
            out[key] = np.load(f)
        elif f.suffix == ".npz":
            with np.load(f) as z:
                out.update({f"{key}:{k}": z[k] for k in z.files})
        elif f.is_file():
            out[key] = f.read_bytes()
    return out


def test_payload_written_in_place_saves_the_same_bytes(tmp_path, monkeypatch):
    from repro.retrieval import SearchParams

    c = corpus_mod.Corpus(corpus_mod.CorpusSpec.from_config(_config(passages=2500)))
    codes, packed = _payload_by_concatenation(c)
    new = c.payload()
    for a, b in zip((codes, packed), new[:2]):
        assert a.dtype == b.dtype and a.shape == b.shape
        np.testing.assert_array_equal(a, b)
    assert corpus_mod.FORMAT_VERSION == 2
    # and the cached index: the program's saved arrays and reference.npz
    saved = {}
    for name, payload in (("appended", lambda: (codes, packed, *new[2:])), ("in_place", c.payload)):
        monkeypatch.setattr(corpus_mod, "CACHE_DIR", tmp_path / name)
        monkeypatch.setattr(c, "payload", payload)
        corpus_mod.load_or_build(c, "plaid", SearchParams(k=10), log=lambda _: None)
        saved[name] = _saved_arrays(tmp_path / name / c.spec.cache_key())
    assert saved["appended"].keys() == saved["in_place"].keys()
    assert any(k.startswith(corpus_mod.REFERENCE_FILE) for k in saved["in_place"])
    for k, a in saved["appended"].items():
        b = saved["in_place"][k]
        if isinstance(a, bytes):
            assert a == b, k
        else:
            assert a.dtype == b.dtype, k
            np.testing.assert_array_equal(a, b, err_msg=k)


# ---- the index cache ---------------------------------------------------------
def test_a_save_made_for_another_configuration_is_refused(tmp_path, monkeypatch):
    from repro import retrieval
    from repro.retrieval import SearchParams

    monkeypatch.setattr(corpus_mod, "CACHE_DIR", tmp_path)
    params = SearchParams(k=10)
    c = corpus_mod.Corpus(corpus_mod.CorpusSpec.from_config(_config(passages=300)))
    corpus_mod.load_or_build(c, "plaid", params, log=lambda _: None)
    path = tmp_path / c.spec.cache_key()
    # the program's own load of a one-index save as plaid-sharded
    with pytest.raises(KeyError, match="n_shards"):
        retrieval.load(str(path), backend="plaid-sharded", params=params)
    # which the harness refuses before loading, naming both layouts
    with pytest.raises(ValueError) as e:
        corpus_mod.load_or_build(c, "plaid-sharded", params, shards=4)
    assert "PlaidRetriever.load" in str(e.value) and "ShardedRetriever.load" in str(e.value)
    assert "backend plaid)" in str(e.value) and "backend plaid-sharded" in str(e.value)
    # a corpus of the same name and seed with 4x the passages
    c4 = corpus_mod.Corpus(corpus_mod.CorpusSpec.from_config(_config(passages=1200)))
    with pytest.raises(ValueError, match="passages: saved 300, configuration 1200"):
        corpus_mod.load_or_build(c4, "plaid", params)
    # a backend that reads the same layout loads it
    r, built = corpus_mod.load_or_build(c, "plaid-pallas", params, log=lambda _: None)
    assert not built and r.index.num_passages == 300


# ---- subprocesses on four host devices ---------------------------------------
def _run(args, cwd, devices=4, timeout=600):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=str(REPO / "src"),
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}")
    env.pop("REPRO_FORCE_INTERPRET", None)
    return subprocess.run(
        [sys.executable, *map(str, args)], cwd=cwd, env=env,
        capture_output=True, text=True, timeout=timeout,
    )


def test_a_cell_whose_chips_differ_from_its_shards_is_refused_before_setup(tmp_path):
    bench_path = make_root(tmp_path)
    bench = json.loads(bench_path.read_text())
    for w in bench["workloads"]:
        if w["name"] == "tiny4.bulk":
            w["chips"] = 1
    bench_path.write_text(json.dumps(bench))
    p = _run([tmp_path / "plaidbench" / "run.py", "--workload", "tiny4.bulk", "--seed", "1",
              "--seconds", "1", "--trace", "0", "--rehearse"], cwd=tmp_path, timeout=120)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "over 4 chips (shards)" in p.stderr and "asks for 1" in p.stderr
    assert "[setup]" not in p.stderr and "[device]" not in p.stderr
    assert not (tmp_path / "plaidbench" / ".index_cache").exists()


_FREE = r"""
import sys
sys.path[:0] = [{root!r}, {repo!r} + "/src", {root!r} + "/plaidbench"]
import jax
from plaidbench import corpus, spec
from repro.retrieval import SearchParams
import run

cfg = spec.load_cell({root!r} + "/BENCHMARK.json", "tiny4.bulk").config
c = corpus.Corpus(corpus.CorpusSpec.from_config(cfg))
r, _ = corpus.load_or_build(c, cfg["backend"], SearchParams(**cfg["search"]),
                            log=lambda _: None, shards=4)
held = list(r._idx_dict.values())
assert held and all(len(a.devices()) == 4 for a in held if a.ndim)
before = len(jax.live_arrays())
run._free(r)
assert all(a.is_deleted() for a in held), "an array of the shards is still live"
live = {{id(a) for a in jax.live_arrays()}}
assert not any(id(a) in live for a in held)
print(len(held), before, len(jax.live_arrays()))
"""


def test_free_deletes_every_array_of_the_sharded_retriever(tmp_path):
    root = make_root(tmp_path).parent
    p = _run(["-c", _FREE.format(root=str(root), repo=str(REPO))], cwd=root)
    assert p.returncode == 0, p.stderr[-3000:]
    held, before, after = map(int, p.stdout.split())
    assert held >= 10 and after <= before - held
