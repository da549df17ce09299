"""The benchmark harness on the CPU: its arithmetic, its loading by name,
the open loop's clock, and one rehearsal through the one command."""
from __future__ import annotations

import json
import os
import pathlib
import shutil
import subprocess
import sys

import numpy as np
import pytest

from plaidbench import check, costs, spec, traffic
from plaidbench.tests.rehearsal import make_root

REPO = pathlib.Path(__file__).resolve().parents[2]


def _env():
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=str(REPO / "src"))
    env.pop("REPRO_FORCE_INTERPRET", None)
    return env


def _run(args, cwd=REPO, timeout=600):
    return subprocess.run(
        [sys.executable, *map(str, args)], cwd=cwd, env=_env(),
        capture_output=True, text=True, timeout=timeout,
    )


# ---- one rehearsal through the one command ---------------------------------
def test_rehearsal_prints_the_result_line(tmp_path):
    root = make_root(tmp_path).parent
    p = _run([root / "plaidbench" / "run.py", "--workload", "tiny.poisson", "--seed", 2**33 + 5,
              "--seconds", "1.5", "--trace", "0", "--rehearse"], cwd=root)
    assert p.returncode == 0, p.stderr[-3000:]
    res = json.loads(p.stdout.strip().splitlines()[-1])
    assert list(res)[:5] == ["correct", "attempted", "failed", "metrics", "device"]
    assert list(res)[-1] == "checks"
    assert res["correct"] is True and res["failed"] == 0 and res["attempted"] >= 10
    assert sorted(res["metrics"]) == ["p50_ms", "recall_k", "setup_s"]
    for m in res["metrics"].values():
        assert m["value"] > 0 and m["unit"]
    assert res["device"]["platform"] == "cpu" and res["device"]["count"] >= 1
    assert set(res["checks"]) == {"unanswered", "bad_answers", "score_gap", "plaid_miss"}
    assert res["checks"]["plaid_miss"]["value"] == 0.0
    # the numbers compared close standard error, each beside its limit
    tail = p.stderr.strip().splitlines()[-4:]
    assert all(line.startswith("check ") and " limit=" in line for line in tail)
    assert "compiles_in_window=0" in p.stderr


def test_cpu_is_refused_without_rehearse(tmp_path):
    root = make_root(tmp_path).parent
    p = _run([root / "plaidbench" / "run.py", "--workload", "tiny.bulk", "--seed", "1",
              "--seconds", "1", "--trace", "0"], cwd=root, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_benchmark_files_alone_do_not_run(tmp_path):
    """A directory holding only BENCHMARK.json and the benchmark's own files
    (no program) exits non-zero and prints no result."""
    shutil.copy(REPO / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(REPO / "plaidbench", tmp_path / "plaidbench",
                    ignore=shutil.ignore_patterns(".index_cache", ".traces", "__pycache__"))
    p = _run([tmp_path / "plaidbench" / "run.py", "--workload", "k1000.bulk",
              "--seed", "1", "--seconds", "1", "--trace", "0"], cwd=tmp_path, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""


# ---- the open loop's clock --------------------------------------------------
class _FakeClock:
    def __init__(self):
        self.t = 100.0

    def __call__(self):
        return self.t

    def sleep(self, d):
        self.t += d


class _Done:
    def __init__(self, clock, gate):
        self.clock, self.gate = clock, gate

    def get(self, timeout=None):
        self.gate.wait(timeout=30)
        return type("R", (), {"scores": np.zeros(2), "pids": np.arange(2)})()


def test_open_loop_times_requests_from_their_due_time():
    import threading

    clock, gate = _FakeClock(), threading.Event()
    due = np.array([0.0, 0.5, 1.0, 1.5])
    stall = {1: 2.0}  # the generator stalls 2 s inside the second submit

    def submit(q):
        i = int(q)
        clock.t += stall.get(i, 0.0)
        if i == len(due) - 1:
            gate.set()  # every result arrives once the last one is sent
        return _Done(clock, gate)

    win = traffic.open_loop(submit, np.arange(4), due, clock=clock, sleep=clock.sleep, lead=0.0)
    t0 = 100.0
    # results are all in hand at t0 + 0.5 + 2.0 (the stall), whatever was sent when
    end = t0 + 2.5
    np.testing.assert_allclose(win.latency_s, end - (t0 + due))
    # requests 2 and 3 were sent late by the stall; 0 and 1 on time
    np.testing.assert_allclose(win.late_s, [0.0, 0.0, 1.5, 1.0])
    assert win.answered.all() and not win.errors


def test_poisson_schedule_offers_every_seed_the_same_gaps():
    a = traffic.poisson_schedule(6.0, 50.0, 1)
    b = traffic.poisson_schedule(6.0, 50.0, 2**33 + 1)
    assert len(a) == len(b) == 300
    assert not np.array_equal(a, b)
    gaps = [np.sort(np.diff(x, prepend=0.0)) for x in (a, b)]
    np.testing.assert_allclose(gaps[0], gaps[1])
    assert a[-1] == pytest.approx(b[-1]) and 45.0 < a[-1] < 50.0


# ---- arithmetic, pinned by hand --------------------------------------------
def test_recall_counts_the_overlap_over_k():
    served = np.array([[1, 2, 3, 4], [5, 6, -1, -1]])
    exact = np.array([[4, 3, 9, 8], [5, 6, 7, 8]])
    assert check.recall(served, exact, 4) == pytest.approx((2 / 4 + 2 / 4) / 2)


def test_plaid_miss_counts_returned_pids_the_plain_stages_do_not_return():
    served = np.array([[1, 2, 3, -1], [5, 6, 7, 8]])
    plain = np.array([[3, 2, 1, 9], [5, 6, 9, -1]])
    assert check.plaid_miss(served, plain) == pytest.approx(2 / 7)
    assert check.plaid_miss(served[:1], plain[:1]) == 0.0
    assert check.plaid_miss(np.full((1, 4), -1), plain[:1]) == 1.0


def test_score_gap_and_bad_answers():
    s = np.array([[3.0, 2.0, 1.0]])
    p = np.array([[7, 8, -1]])
    ref = np.array([[3.0, 2.5, np.nan]])
    assert check.score_gap(s, p, ref) == pytest.approx(0.5)  # the pad is skipped
    assert check.score_gap(s, np.array([[7, 8, 9]]), ref) == np.inf  # unknown pid
    good = [np.array([3.0, 2.0, -1e30]), None]
    pids = [np.array([1, 2, -1]), None]  # an unanswered request is not bad
    assert check.bad_answers(good, pids, 3, 10) == 0
    assert check.bad_answers([np.array([2.0, 3.0, 1.0])], [np.array([1, 2, 3])], 3, 10) == 1
    assert check.bad_answers([np.array([3.0, 2.0, 1.0])], [np.array([1, 1, 3])], 3, 10) == 1
    assert check.bad_answers([np.array([3.0, 2.0, 1.0])], [np.array([1, -1, 3])], 3, 10) == 1
    assert check.bad_answers([np.array([3.0, 2.0, 1.0])], [np.array([1, 2, 10])], 3, 10) == 1
    ok, checks = check.verdict({"a": 0, "b": 2e-4}, {"a": 0, "b": 1e-3})
    assert ok and checks["b"] == {"value": 2e-4, "limit": 1e-3}
    assert not check.verdict({"a": 1}, {"a": 0})[0]


def test_step_flops_and_mfu():
    # k=10: stage 1 over 262,144 centroids, stage 4 over 64 passages of 64
    f = costs.query_flops(K=262144, d=128, nq=32, ndocs=256, k=10, mean_len=64.0)
    assert f == 2 * 262144 * 128 * 32 + 2 * 64 * 64 * 128 * 32 == 2_181_038_080
    read = spec.load_reader(REPO, "step_mfu.bulk")
    from repro.retrieval import SearchParams

    ctx = {
        "config": {"centroids": 262144, "dim": 128, "q_len": 32},
        "params": SearchParams(k=10, ndocs=256), "mean_len": 64.0, "qps": 10.0,
        "chips": 1, "peaks": {"bf16_flops_per_s": 197e12},
    }
    assert read(ctx) == pytest.approx(100 * 2_181_038_080 * 10 / 197e12)
    assert read(dict(ctx, peaks=None)) is None


def test_roofline_arithmetic():
    c = costs.interaction_cost(B=1, nd=32, L=128, nq=32)
    # one (32, 32, 128) f32 block in, 32 scores out, the lane's q_mask
    assert c["bytes"] == 32 * 32 * 128 * 4 + 32 * 4 + 32 * 4
    assert c["flops"] == 2 * 32 * 128 * 32
    s4 = costs.stage4_cost(B=2, nd=8, L=128, d=128, pd=32, nq=32, nbits=2)
    rows = 8 * 128
    assert s4["bytes"] == 2 * (rows * 128 * 4 + rows * 32 + rows * 4 + 8 * 4) + 2 * (32 * 128 * 4 + 32 * 4) + 4 * 4
    assert s4["flops"] == 2 * 2 * 8 * 128 * 128 * 32
    peaks = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e9}
    share, bound = costs.roofline_share({"bytes": 2e6, "flops": 1e9}, 0.004, peaks)
    assert bound == "bytes" and share == pytest.approx(50.0)
    share, bound = costs.roofline_share({"bytes": 1e3, "flops": 3e9}, 0.004, peaks)
    assert bound == "flops" and share == pytest.approx(75.0)


# ---- the generator ----------------------------------------------------------
def _tiny_spec(**kw):
    cfg = json.load(open(REPO / "plaidbench" / "tests" / "data" / "rehearsal.k10.json"))
    cfg.update(passages=300, **kw)
    from plaidbench.corpus import CorpusSpec

    return CorpusSpec.from_config(cfg)


def test_index_generator_is_deterministic_for_a_corpus_seed():
    import jax.numpy as jnp

    from plaidbench.corpus import BLOCK, Corpus

    pids = jnp.asarray(np.r_[np.arange(0, 300, 7), -np.ones(BLOCK - 43)].astype(np.int32))
    a, b, c = Corpus(_tiny_spec()), Corpus(_tiny_spec()), Corpus(_tiny_spec(corpus_seed=1))
    np.testing.assert_array_equal(a.lens, b.lens)
    np.testing.assert_array_equal(np.asarray(a.cutoffs), np.asarray(b.cutoffs))
    for x, y in zip(a.payload_block(pids), b.payload_block(pids)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    assert not np.array_equal(np.asarray(a.payload_block(pids)[0]), np.asarray(c.payload_block(pids)[0]))
    codes, packed, pairs, lists = a.payload()
    assert codes.shape == (a.num_tokens,) and packed.shape == (a.num_tokens, 32)
    # pairs: unique (code, pid) rows in np.unique's order
    pid_of = np.repeat(np.arange(300), a.lens)
    want = np.unique(np.stack([codes, pid_of], 1).astype(np.int64), axis=0)
    np.testing.assert_array_equal(pairs, want)
    # the benchmark's own inverted lists hold the same rows, both ways round
    po, co = lists["pid_off"], lists["code_off"]
    for pid in (0, 7, 299):
        np.testing.assert_array_equal(lists["pid_codes"][po[pid]:po[pid + 1]],
                                      want[want[:, 1] == pid, 0])
    for code in np.unique(codes)[[0, 5, -1]]:
        np.testing.assert_array_equal(lists["code_pids"][co[code]:co[code + 1]],
                                      want[want[:, 0] == code, 1])
    assert co[-1] == po[-1] == len(want)
    qa, pa = a.queries(5, 9)
    qb, pb = b.queries(5, 9)
    np.testing.assert_array_equal(qa, qb)
    np.testing.assert_array_equal(pa, pb)
    np.testing.assert_allclose(np.linalg.norm(qa, axis=-1), 1.0, rtol=1e-5)


# ---- loading by name --------------------------------------------------------
def test_peaks_refuse_an_unknown_device_kind():
    assert spec.peaks_for(REPO, "TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="not in plaidbench/peaks.json"):
        spec.peaks_for(REPO, "TPU v9 imaginary")


def test_config_traffic_and_metric_each_load_from_their_own_file(tmp_path):
    """A later PR adds a configuration, a traffic mix and a per-layer metric
    with new files and BENCHMARK.json entries alone."""
    bench_path = make_root(tmp_path)
    pb = tmp_path / "plaidbench"
    bench = json.loads(bench_path.read_text())
    cfg = json.loads((pb / "configs" / "rehearsal.k10.json").read_text())
    cfg.update(name="added.k5", search=dict(cfg["search"], k=5))
    (pb / "configs" / "added.k5.json").write_text(json.dumps(cfg))
    (pb / "traffic" / "added-mix.json").write_text(json.dumps(
        {"kind": "closed_loop", "batch": 2, "pool": 8, "check_sample": 4, "plaid_sample": 4}))
    (pb / "metrics" / "added_metric.bulk.py").write_text(
        "def read(ctx):\n    return 41.0 + ctx['qps']\n")
    bench["configs"].append({"name": "added.k5", "source": "x", "file": "plaidbench/configs/added.k5.json",
                             "reduced": [], "why": "added"})
    bench["workloads"].append({"name": "added.bulk", "config": "added.k5", "traffic": "added-mix",
                               "chips": 1, "why": "added"})
    bench["per_layer"].append({"name": "added_metric.bulk", "unit": "ms", "better": "lower",
                               "source": "host_clock", "layer": "serving", "moves": "qps",
                               "workloads": ["added.bulk"]})
    for m in bench["end_to_end"]:
        if m["name"] == "qps":
            m["workloads"].append("added.bulk")
    bench_path.write_text(json.dumps(bench))
    cell = spec.load_cell(bench_path, "added.bulk")
    assert cell.config["search"]["k"] == 5 and cell.traffic["batch"] == 2
    assert [m["name"] for m in cell.per_layer()] == ["added_metric.bulk"]
    assert {m["name"] for m in cell.end_to_end()} == {"qps", "recall_k", "setup_s"}
    assert spec.load_reader(tmp_path, "added_metric.bulk")({"qps": 1.0}) == 42.0
    with pytest.raises(KeyError):
        spec.load_cell(bench_path, "no.such.cell")


def test_the_benchmark_names_its_cells_pieces():
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    for w in bench["workloads"]:
        cell = spec.load_cell(REPO / "BENCHMARK.json", w["name"])
        assert cell.config["name"] == w["config"]
        assert any(m["name"] == "setup_s" for m in cell.end_to_end())
        assert len(cell.end_to_end()) >= 2 and cell.per_layer()
        for m in cell.per_layer():
            assert callable(spec.load_reader(REPO, m["name"]))
