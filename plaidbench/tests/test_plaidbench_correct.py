"""``correct`` on the CPU: a sound rehearsal passes, and the comparison
refuses the control (the reference one precision down) and each fault the
cells can have, planted under the timed path while the rest of a run goes
on as on the chip."""
from __future__ import annotations

import json
import os
import pathlib
import subprocess
import sys

import pytest

from plaidbench import check
from plaidbench.tests.rehearsal import make_root

REPO = pathlib.Path(__file__).resolve().parents[2]

#: Runs plaidbench/run.py with the program's search broken underneath it.
_RUNNER = r"""
import dataclasses, sys
import numpy as np
sys.path[:0] = [{root!r}, {repo!r} + "/src", {root!r} + "/plaidbench"]
from repro.retrieval import backends
fault = sys.argv.pop(1)
search = backends.PlaidRetriever.search_batch
cut = {{}}

def broken(self, qs, q_masks=None, **kw):
    if fault == "cap":  # stage 1's candidate set cut to a sixteenth of its cap, below every
        if id(self) not in cut:  # query's probed set, so that each sampled answer can miss
            p = self.params
            cut[id(self)] = type(self)(self.index, p.replace(candidate_cap=p.candidate_cap // 16))
        return search(cut[id(self)], qs, q_masks, **kw)
    res = search(self, qs, q_masks, **kw)
    s, p = np.array(res.scores), np.array(res.pids)
    if fault == "pid":  # an answer altered where it is produced
        p[:, 0] = (p[:, 0] + 1) % self.index.num_passages
    elif fault == "score":
        s[:, 0] += 0.01
    elif fault == "half":  # half the batch left out, answered from the rest
        h = len(p) // 2
        s[h:], p[h:] = s[: len(p) - h], p[: len(p) - h]
    return dataclasses.replace(res, scores=s, pids=p)

backends.PlaidRetriever.search_batch = broken
import run
run.main(sys.argv[1:])
"""


def _run(args, cwd, timeout=600):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=str(REPO / "src"))
    env.pop("REPRO_FORCE_INTERPRET", None)
    return subprocess.run(
        [sys.executable, *map(str, args)], cwd=cwd, env=env,
        capture_output=True, text=True, timeout=timeout,
    )


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return make_root(tmp_path_factory.mktemp("root")).parent


@pytest.mark.parametrize("fault", ["none", "pid", "score", "half", "cap"])
def test_a_fault_under_the_timed_path_is_not_correct(root, fault):
    code = _RUNNER.format(repo=str(REPO), root=str(root))
    p = _run(["-c", code, fault, "--workload", "tiny.bulk", "--seed", 17 + len(fault),
              "--seconds", "1", "--trace", "0", "--rehearse"], cwd=root)
    assert p.returncode == 0, p.stderr[-3000:]
    res = json.loads(p.stdout.strip().splitlines()[-1])
    assert res["correct"] is (fault == "none"), res["checks"]
    if fault == "cap":  # exact scores of the passages it did return: only stages 1-3 see it
        assert res["checks"]["score_gap"]["value"] <= res["checks"]["score_gap"]["limit"]
        assert res["checks"]["plaid_miss"]["value"] > res["checks"]["plaid_miss"]["limit"]


def test_the_control_is_not_correct(root):
    """PLAID's stages run plainly in bfloat16, in the program's place, fail
    the cell's limits on every seed; the program passes them."""
    p = _run([root / "plaidbench" / "control.py", "--workload", "tiny.bulk",
              "--seeds", "3", "4", "5", "--rehearse"], cwd=root)
    assert p.returncode == 0, p.stderr[-3000:]
    rows = [json.loads(x) for x in p.stdout.strip().splitlines()]
    cfg = json.loads((root / "plaidbench" / "configs" / "rehearsal.k10.json").read_text())
    limits = cfg["limits"]

    def verdict(r):
        return check.verdict({"unanswered": 0, **{n: r[n] for n in limits if n != "unanswered"}},
                             limits)[0]

    assert len(rows) == 3
    for r in rows:
        assert set(r) >= {"program", "bf16_path", "reference_bf16"}
        assert verdict(r["program"]) and r["program"]["plaid_miss"] == 0.0, r
        assert not verdict(r["reference_bf16"]), r
