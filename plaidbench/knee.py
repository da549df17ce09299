"""Find the highest rate an open-loop cell sustains: one sweep, one process.

    python3 plaidbench/knee.py --workload k10.poisson --rates 2 4 6 8 --seconds 40

For each offered rate, in one process over one loaded index, Poisson
arrivals (``traffic.poisson_schedule``) run for ``--seconds`` into a server
set up as the cell's window sets it up, and the line printed gives the
completed rate and the median latency of the first and the last third of
the requests.  A rate is kept up with when the server completes at least
98 % of it and the last third's median latency is under twice the first
third's (a queue that grows all through the run fails both).  The knee is
the highest rate swept at and below which every rate is kept up with: above
it the server can fall into a regime of full batches, which it may keep up
with at some rates and not at others.  The cell's traffic file then takes
0.8 x the knee as a number.
"""
from __future__ import annotations

import argparse
import json
import sys

from run import ROOT, _Annotated, _device_check, _params, _server, _warm, log  # noqa: F401


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)

    from plaidbench import corpus as corpus_mod, spec as spec_mod, traffic as tr
    from repro import compile_cache

    compile_cache.configure()
    import numpy as np

    cell = spec_mod.load_cell(ROOT / "BENCHMARK.json", args.workload)
    _device_check(cell.workload["chips"], rehearse=False)
    cfg, t = cell.config, cell.traffic
    params = _params(cfg)
    corpus = corpus_mod.Corpus(corpus_mod.CorpusSpec.from_config(cfg))
    r, _ = corpus_mod.load_or_build(
        corpus, cfg["backend"], params, log=log, shards=spec_mod.shards(cfg))
    n = int(max(args.rates) * args.seconds) + 1
    qs, _ = corpus.queries(n, args.seed)
    _warm(r, qs, t, params)

    for rate in args.rates:
        due = tr.poisson_schedule(rate, args.seconds, args.seed)
        srv = _server(r, t, tracer=None)
        try:
            win = tr.open_loop(srv.submit, qs[: len(due)], due)
        finally:
            srv.shutdown(drain=True, timeout=300)
        lat = win.latency_s * 1e3
        third = max(1, len(lat) // 3)
        early, late = lat[:third], lat[-third:]
        row = {
            "offered_qps": rate, "requests": len(due),
            "completed_qps": float(win.answered.sum() / win.seconds),
            "p50_ms": float(np.nanpercentile(lat, 50)),
            "p95_ms": float(np.nanpercentile(lat, 95)),
            "early_p50_ms": float(np.nanpercentile(early, 50)),
            "late_p50_ms": float(np.nanpercentile(late, 50)),
            "buckets": srv.stats().get("buckets"),
        }
        row["keeps_up"] = bool(row["completed_qps"] >= 0.98 * rate
                               and row["late_p50_ms"] < 2 * row["early_p50_ms"])
        print(json.dumps(row), flush=True)


if __name__ == "__main__":
    sys.exit(main())
