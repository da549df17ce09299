"""A cell whose corpus is split over four chips, rehearsed on four host CPU
devices through the one command: ``plaid-sharded`` is correct against PLAID
run shard by shard, and the same configuration served by one index over all
passages is not (the cap binds the one index and no shard)."""
from __future__ import annotations

import json
import os
import pathlib
import subprocess
import sys

import pytest

from plaidbench import check
from plaidbench.tests.rehearsal import SHARDED, SHARDED_CAP, make_root

REPO = pathlib.Path(__file__).resolve().parents[2]

#: Runs plaidbench/run.py with one thing swapped underneath it.
_RUNNER = r"""
import sys
sys.path[:0] = [{root!r}, {repo!r} + "/src", {root!r} + "/plaidbench"]
from plaidbench import corpus, reference
swap = sys.argv.pop(1)
if swap == "one_index_program":  # the configuration served by one index over all passages
    build = corpus.load_or_build
    corpus.load_or_build = lambda c, backend, *a, **kw: build(c, "plaid", *a, **kw)
elif swap == "one_index_reference":  # judged against one PLAID index over all passages
    topk = reference.plaid_topk
    reference.plaid_topk = lambda *a, shards=1, **kw: topk(*a, **kw)
import run
run.main(sys.argv[1:])
"""


def _run(args, cwd, timeout=600):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=str(REPO / "src"),
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    env.pop("REPRO_FORCE_INTERPRET", None)
    return subprocess.run(
        [sys.executable, *map(str, args)], cwd=cwd, env=env,
        capture_output=True, text=True, timeout=timeout,
    )


def _cell(root, workload, seed, swap=None):
    args = [root / "plaidbench" / "run.py"]
    if swap:
        args = ["-c", _RUNNER.format(root=str(root), repo=str(REPO)), swap]
    p = _run([*args, "--workload", workload, "--seed", seed, "--seconds", "1.5",
              "--trace", "0", "--rehearse"], cwd=root)
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1]), p.stderr


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return make_root(tmp_path_factory.mktemp("root")).parent


@pytest.mark.parametrize("workload", ["tiny4.bulk", "tiny4.poisson"])
def test_the_split_corpus_served_by_plaid_sharded_is_correct(root, workload):
    res, err = _cell(root, workload, 2**33 + 11)
    assert res["correct"] is True, res["checks"]
    assert res["checks"]["plaid_miss"]["value"] == 0.0
    assert res["device"]["count"] == 4 and res["failed"] == 0
    assert "compiles_in_window=0" in err
    ref = next(line for line in err.splitlines() if line.startswith("[reference]"))
    reached = int(ref.split("probed_passages_max=")[1].split()[0])
    assert f"shards=4 cap={SHARDED_CAP}" in ref and reached < SHARDED_CAP


@pytest.mark.parametrize("swap", ["one_index_program", "one_index_reference"])
def test_one_index_over_the_split_corpus_is_not_correct(tmp_path, swap):
    """The configuration served by ``plaid`` over all passages (the planted
    fault) is refused; so is ``plaid-sharded`` judged against one index over
    all passages, which is why the reference runs shard by shard."""
    root = make_root(tmp_path).parent
    res, _ = _cell(root, "tiny4.bulk", 2**33 + 11, swap=swap)
    assert res["correct"] is False, res["checks"]
    c = res["checks"]
    assert c["plaid_miss"]["value"] > c["plaid_miss"]["limit"]
    assert c["score_gap"]["value"] <= c["score_gap"]["limit"]  # exact scores, other passages


def test_the_control_runs_shard_by_shard(root):
    """``control.py`` on the split corpus: the program reads 0 against the
    per-shard reference; the reference in bfloat16 in its place fails."""
    p = _run([root / "plaidbench" / "control.py", "--workload", "tiny4.bulk",
              "--seeds", "6", "--rehearse"], cwd=root)
    assert p.returncode == 0, p.stderr[-3000:]
    (row,) = [json.loads(x) for x in p.stdout.strip().splitlines()]
    cfg = json.loads((root / "plaidbench" / "configs" / f"{SHARDED}.json").read_text())
    limits = cfg["limits"]

    def verdict(r):
        return check.verdict({"unanswered": 0, **{n: r[n] for n in limits if n != "unanswered"}},
                             limits)[0]

    assert row["probed_passages_max"] < SHARDED_CAP
    assert verdict(row["program"]) and row["program"]["plaid_miss"] == 0.0, row
    assert not verdict(row["reference_bf16"]), row
