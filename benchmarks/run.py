"""Benchmark driver: one module per paper table/figure.

``PYTHONPATH=src python -m benchmarks.run [--only table3] [--dry]`` prints
``bench,case,key=value,...`` CSV-ish lines (machine-greppable) and a summary.
``--dry`` shrinks corpora/query counts to smoke-test the full pipeline in CI
(numbers are NOT meaningful at dry scale).  ``--json PATH`` additionally
writes every result row as structured JSON (bench/case/values + run
metadata) — the artifact CI uploads per run so perf enters the trajectory.
``--trace PATH`` exports every span the benchmarks recorded (the process
tracer: fig2 stage spans, serving queue/dispatch spans, live-index
mutations) as Chrome trace-event JSON — load it in Perfetto.
"""
from __future__ import annotations

import argparse
import json
import os
import time

#: Default directory for ``--json`` / ``--trace`` artifacts given as bare
#: filenames — keeps generated output out of the repo root (``out/`` is
#: gitignored).  Paths that already carry a directory are used as-is.
OUT_DIR = "out"


def _artifact_path(path: str | None) -> str | None:
    if path is None:
        return None
    if not os.path.dirname(path):
        path = os.path.join(OUT_DIR, path)
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    return path

#: Version of the ``--json`` payload layout.  Bump ONLY on breaking schema
#: changes (renamed/removed keys); adding record fields is backward
#: compatible.  ``benchmarks.bench_diff`` refuses to compare payloads with
#: mismatched major versions.
#: v2: added observability sections (``metrics`` registry snapshot +
#: ``span_summary`` per-span-name rollup); ``results`` rows are unchanged,
#: and bench_diff treats v1<->v2 as comparable.
#: v3: added the optional top-level ``pareto`` section (the quality
#: harness's (work, recall) frontier, see ``benchmarks.quality_sweep``);
#: ``results`` rows are still unchanged, so v1/v2/v3 all compare.
SCHEMA_VERSION = 3

BENCHES = [
    "table3_endtoend",
    "fig2_breakdown",
    "fig3_centroid_recall",
    "fig4_score_cdf",
    "fig6_ablation",
    "fig7_scaling",
    "fig8_parallel",
    "batched_throughput",  # q/s vs batch size + bursty open-loop serving:
    # fixed vs bucketed dispatch (q/s, p50/p99, shed rate)
    "roofline_report",  # HLO cost model of the batched pipeline
    "live_ingest",  # streaming ingest + latency vs delta count + compaction
    "sharded_live",  # latency vs shard-count x delta-segment-count sweep
    "index_build",  # streaming vs monolithic build: throughput + host memory
    "tiered_scale",  # beyond-HBM tiered storage: footprint ratio, per-batch
    # candidate-slice transfer bytes (gated vs resident footprint), identity
    "quality_sweep",  # retrieval-quality harness: t_cs x nprobe x ndocs
    # Pareto sweep (bucketed-cap engine), lossless-caps backend
    # certification, pruned-index quality/footprint trade
]


def _jsonable(v):
    """Coerce benchmark values (numpy scalars etc.) into JSON-safe types."""
    if isinstance(v, (str, int, float, bool)) or v is None:
        return v
    try:
        import numpy as np

        if isinstance(v, np.generic):
            return v.item()
    except ImportError:  # pragma: no cover
        pass
    return str(v)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None, help="substring filter")
    ap.add_argument("--dry", action="store_true",
                    help="tiny corpora / single trial: CI smoke run")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="also write results as machine-readable JSON "
                         "(bare filenames land under out/)")
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="export recorded spans as Chrome trace-event JSON "
                         "(Perfetto-loadable; bare filenames land under "
                         "out/)")
    args = ap.parse_args()
    from repro import compile_cache

    compile_cache.configure()
    args.json = _artifact_path(args.json)
    args.trace = _artifact_path(args.trace)

    rows = []
    records = []

    def emit(bench, case, **kv):
        parts = ",".join(f"{k}={v}" for k, v in kv.items())
        line = f"{bench},{case},{parts}"
        rows.append(line)
        records.append(
            dict(bench=bench, case=case,
                 **{k: _jsonable(v) for k, v in kv.items()})
        )
        print(line, flush=True)

    import importlib

    t_start = time.time()
    ran_modules = []
    for name in BENCHES:
        if args.only and args.only not in name:
            continue
        mod = importlib.import_module(f"benchmarks.{name}")
        t0 = time.time()
        print(f"# --- {name} ---", flush=True)
        mod.run(emit, dry=args.dry)
        ran_modules.append(mod)
        print(f"# {name} done in {time.time() - t0:.1f}s", flush=True)

    print(f"# total {len(rows)} results")

    from repro.obs.metrics import get_registry
    from repro.obs.trace import get_tracer

    if args.trace:
        n_events = get_tracer().export(args.trace)
        print(f"# wrote {n_events} trace events to {args.trace}")

    if args.json:
        import platform

        try:
            import jax

            jax_meta = dict(
                jax_version=jax.__version__,
                backend=jax.default_backend(),
                n_devices=len(jax.devices()),
            )
        except ImportError:  # pragma: no cover
            jax_meta = {}
        payload = dict(
            schema_version=SCHEMA_VERSION,
            dry=args.dry,
            only=args.only,
            finished_unix=time.time(),
            wall_s=time.time() - t_start,
            python=platform.python_version(),
            **jax_meta,
            results=records,
            metrics=get_registry().snapshot(),
            span_summary=get_tracer().summary(),
        )
        # benches may contribute extra top-level payload sections (e.g.
        # quality_sweep's ``pareto`` frontier, schema v3)
        for mod in ran_modules:
            if hasattr(mod, "payload_sections"):
                payload.update(mod.payload_sections())
        with open(args.json, "w") as f:
            json.dump(payload, f, indent=2)
        print(f"# wrote {len(records)} records to {args.json}")


if __name__ == "__main__":
    main()
