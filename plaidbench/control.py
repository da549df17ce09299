"""Readings that the limits of ``correct`` are set from, many seeds in one
process.

    python3 plaidbench/control.py --workload k1000.bulk --seeds 1 2 3 4 5 6

For each seed, the queries a run of the cell draws and the samples that run
checks.  The program's retriever (loaded once) answers them at the cell's
batch, as the window drives it, and ``check`` compares the answers with the
float32 references as a run does: ``score_gap`` and ``plaid_miss``, the
lower readings.  On the first ``CONTROLS`` seeds two controls answer the
same queries and are compared the same way; the limits have to refuse them:

* ``bf16_path``: the program with its own bfloat16 path for the centroid
  scores of stages 1 to 3 switched on (``SearchParams.score_dtype``);
* ``reference_bf16``: PLAID's four stages run plainly in bfloat16
  (``reference.plaid_topk(low=True)``) in the program's place.

No measured window runs: the answers of a sound program do not depend on
when they were asked.
"""
from __future__ import annotations

import argparse
import json
import sys
import time

from run import ROOT, _device_check, _params, log  # noqa: F401

#: Seeds (the first of ``--seeds``) on which the controls answer too.
CONTROLS = 3


def _answers(r, qs, batch: int):
    """The retriever's (scores, pids) for ``qs``, ``batch`` at a time (the
    last batch filled up with the first query)."""
    import numpy as np

    out_s, out_p = [], []
    for i in range(0, len(qs), batch):
        b = qs[i : i + batch]
        n = len(b)
        if n < batch:
            b = np.concatenate([b, np.repeat(b[:1], batch - n, axis=0)])
        res = r.search_batch(b)
        out_s.append(np.asarray(res.scores)[:n])
        out_p.append(np.asarray(res.pids)[:n])
    return np.concatenate(out_s), np.concatenate(out_p)


def _with_params(r, params):
    """The same retriever over the same device arrays at other settings."""
    if hasattr(r, "index"):
        return type(r)(r.index, params)
    return type(r)(  # plaid-sharded holds its shards' arrays, not an index
        r._idx_dict, r._meta, docs_per_shard=r.docs_per_shard, n_shards=r.n_shards,
        params=params, mesh=r.mesh,
    )


def _drawn(cell, corpus, seed: int):
    """The queries a run draws, and the sample of them whose answers it
    compares with the plain four stages."""
    import numpy as np

    from plaidbench import check, traffic as tr

    t = cell.traffic
    if t["kind"] == "open_loop":
        n = len(tr.poisson_schedule(t["rate_qps"], cell.bench["run_seconds"], seed))
    else:
        n = t["pool"]
    qs, _ = corpus.queries(n, seed)
    return qs, check.sample(np.ones(n, bool), t["plaid_sample"], seed, stream=4)


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)

    from plaidbench import check, corpus as corpus_mod, reference, spec as spec_mod
    from repro import compile_cache

    compile_cache.configure()
    cell = spec_mod.load_cell(ROOT / "BENCHMARK.json", args.workload)
    _device_check(cell.workload["chips"], args.rehearse)
    cfg, t = cell.config, cell.traffic
    shards = spec_mod.shards(cfg)
    k = cfg["search"]["k"]
    batch = t["batch"] if "batch" in t else t["batch_size"]  # a shape the window runs
    params = _params(cfg)
    corpus = corpus_mod.Corpus(corpus_mod.CorpusSpec.from_config(cfg))
    r, _ = corpus_mod.load_or_build(corpus, cfg["backend"], params, log=log, shards=shards)
    bf16 = _with_params(r, r.params.replace(score_dtype="bfloat16"))

    # the program's answers first, then its state is freed for the references
    drawn, answers = {}, {}
    for j, seed in enumerate(args.seeds):
        qs, pidx = drawn[seed] = _drawn(cell, corpus, seed)
        answers[seed] = {"program": _answers(r, qs[pidx], batch)}
        if j < CONTROLS:
            answers[seed]["bf16_path"] = _answers(bf16, qs[pidx], batch)
        log(f"[control] seed={seed} answered")
    from run import _free

    _free(r)
    del r, bf16
    lists = corpus_mod.load_inverted_lists(corpus)
    for j, seed in enumerate(args.seeds):
        qs, pidx = drawn[seed]
        t0 = time.perf_counter()
        plain_s, plain_p, reached = reference.plaid_topk(
            corpus, lists, qs[pidx], cfg["search"], shards=shards)
        row = {"workload": cell.name, "seed": seed, "plaid_sample": len(pidx),
               "probed_passages_max": int(reached.max()),
               "plain_seconds": time.perf_counter() - t0}
        if j < CONTROLS:
            answers[seed]["reference_bf16"] = reference.plaid_topk(
                corpus, lists, qs[pidx], cfg["search"], shards=shards, low=True)[:2]
        for name, (s, p) in answers[seed].items():
            ref = reference.score_pids(corpus, qs[pidx], p)
            row[name] = {
                "plaid_miss": check.plaid_miss(p, plain_p),
                "score_gap": check.score_gap(s, p, ref),
                "bad_answers": check.bad_answers(list(s), list(p), k, cfg["passages"]),
                "recall_vs_plain": check.recall(p, plain_p, k),
            }
        print(json.dumps(row), flush=True)


if __name__ == "__main__":
    sys.exit(main())
