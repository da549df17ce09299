"""Run one benchmark cell once on the chip and print its result line.

    python3 plaidbench/run.py --workload k10.poisson --seed 7 --seconds 48 --trace 0

The cell (``BENCHMARK.json``'s ``workloads`` entry) names a configuration
file and a traffic file; the run

1. refuses a CPU (``--rehearse`` runs on the CPU, for the harness's tests);
2. makes or loads the cell's index (``corpus.load_or_build``) and the
   traffic's queries from ``--seed``, and warms every shape the window uses:
   set-up, reported as ``setup_s``;
3. drives the program for ``--seconds`` through ``BatchingServer.submit``
   (open loop) or the retriever's ``search_batch`` (closed loop), with the
   profiler on around the window when ``--trace 1``;
4. reads the device's peak memory, frees the program, and compares seeded
   samples of the answers with the float32 references, the exhaustive one
   and PLAID's own four stages run plainly (``check``): ``correct``;
5. prints the end-to-end metrics (``--trace 0``) or the per-layer ones
   (``--trace 1``), as the last line of standard output, in one JSON object.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parent.parent
for p in (ROOT, ROOT / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def _device_check(chips: int, rehearse: bool):
    import jax

    devices = jax.devices()
    d0 = devices[0]
    log(f"[device] platform={d0.platform} kind={d0.device_kind!r} count={len(devices)}")
    if not rehearse:
        if d0.platform != "tpu":
            raise SystemExit(f"plaidbench: no TPU (platform {d0.platform!r}); --rehearse runs on the CPU")
        if os.environ.get("REPRO_FORCE_INTERPRET") is not None:
            raise SystemExit("plaidbench: REPRO_FORCE_INTERPRET is set; the kernels must compile with Mosaic")
    if len(devices) < chips:
        raise SystemExit(f"plaidbench: the cell needs {chips} chips, {len(devices)} visible")
    return devices[:chips]


class _CompileCounter:
    """Counts the programs compiled (or loaded from the persistent cache)."""

    def __init__(self):
        import jax.monitoring

        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.n += 1


class _Annotated:
    """The retriever as the server sees it, each dispatch inside a
    ``plaidbench.dispatch`` profiler span."""

    def __init__(self, r):
        self._r = r
        self.params = r.params

    def describe(self):
        return self._r.describe()

    def search_batch(self, qs, q_masks=None, *, t_cs=None):
        import jax.profiler

        with jax.profiler.TraceAnnotation("plaidbench.dispatch"):
            return self._r.search_batch(qs, q_masks, t_cs=t_cs)


def _params(cfg: dict):
    """The configuration's search settings, with JAX's default matmul
    precision set to the configuration's (the program's float32 dots run
    at it)."""
    import jax

    from repro.retrieval import SearchParams

    jax.config.update("jax_default_matmul_precision", cfg["matmul_precision"])
    return SearchParams(**cfg["search"])


def _warm(r, qs, traffic: dict, params) -> None:
    """Run every shape the window will run, as the window runs it."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    if traffic["kind"] == "open_loop":
        for b in traffic["buckets"]:
            ts = np.full(b, params.t_cs, np.float32)
            out = r.search_batch(jnp.asarray(qs[:b]), t_cs=jnp.asarray(ts))
            jax.block_until_ready(out.pids)
    else:
        for _ in range(2):
            r.search_batch(qs[: traffic["batch"]])


def _server(r, t: dict, tracer):
    """The open-loop cell's server, as its traffic file sets it up."""
    from repro.serving import BatchingServer

    return BatchingServer(
        _Annotated(r), batch_size=t["batch_size"], max_wait_ms=t["max_wait_ms"],
        bucketed=t["bucketed"], tracer=tracer,
    )


def _window(cell, r, qs, seconds: float, seed: int, tracer):
    """Drive the window; returns (Window, extra log fields)."""
    import jax.profiler
    import numpy as np

    from plaidbench import traffic as tr

    t = cell.traffic
    if t["kind"] == "open_loop":
        due = tr.poisson_schedule(t["rate_qps"], seconds, seed)
        srv = _server(r, t, tracer)
        try:
            win = tr.open_loop(srv.submit, qs, due)
        finally:
            srv.shutdown(drain=True, timeout=120)
        late = win.late_s[np.isfinite(win.late_s)]
        extra = {
            "offered": len(due), "rate_qps": t["rate_qps"],
            "late_p95_ms": float(np.percentile(late, 95) * 1e3) if len(late) else float("nan"),
            "late_max_ms": float(late.max() * 1e3) if len(late) else float("nan"),
            "buckets": srv.stats().get("buckets"),
        }
        return win, extra
    win = tr.closed_loop(
        lambda q: tuple(r.search_batch(q)), qs, t["batch"], seconds,
        annotate=lambda: jax.profiler.TraceAnnotation("plaidbench.bulk_call"),
    )
    return win, {"batches": win.n // t["batch"], "batch": t["batch"]}


def _device_arrays(obj, seen: set):
    """Every ``jax.Array`` that ``obj`` holds: the leaves of each of its
    attributes as a pytree, and those of the program's own objects inside
    them (an engine, an index that is not a pytree), each object once."""
    import jax

    if id(obj) in seen or not hasattr(obj, "__dict__"):
        return
    seen.add(id(obj))
    for value in vars(obj).values():
        for leaf in jax.tree.leaves(value):
            if isinstance(leaf, jax.Array):
                yield leaf
            elif type(leaf).__module__.startswith("repro."):
                yield from _device_arrays(leaf, seen)


def _free(r) -> None:
    """Delete the program's device state before the reference runs."""
    for leaf in list(_device_arrays(r, set())):
        if not leaf.is_deleted():
            leaf.delete()
    gc.collect()


def _finite(x):
    if isinstance(x, float) and not math.isfinite(x):
        return str(x)
    return x


def run(argv=None) -> dict:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="run on the CPU at the cell's size (for tests; not a measurement)")
    args = ap.parse_args(argv)

    from plaidbench import spec as spec_mod

    cell = spec_mod.load_cell(ROOT / "BENCHMARK.json", args.workload)
    root = cell.root
    shards = spec_mod.shards(cell.config)
    if shards > 1 and cell.workload["chips"] != shards:
        raise SystemExit(
            f"plaidbench: configuration {cell.config['name']!r} splits its passages over "
            f"{shards} chips (shards) and cell {cell.name!r} asks for {cell.workload['chips']}"
        )

    from repro import compile_cache

    cache_dir = compile_cache.configure()
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    devices = _device_check(cell.workload["chips"], args.rehearse)
    kind = devices[0].device_kind
    peaks = None if args.rehearse else spec_mod.peaks_for(root, kind)
    log(f"[setup] workload={cell.name} seed={args.seed} seconds={args.seconds} "
        f"trace={args.trace} compile_cache={cache_dir}")

    import numpy as np

    from plaidbench import check, corpus as corpus_mod, reference
    from repro.obs.trace import Tracer

    cfg, t = cell.config, cell.traffic
    params = _params(cfg)
    corpus = corpus_mod.Corpus(corpus_mod.CorpusSpec.from_config(cfg))
    r, built = corpus_mod.load_or_build(corpus, cfg["backend"], params, log=log, shards=shards)
    if t["kind"] == "open_loop":
        from plaidbench.traffic import poisson_schedule

        n_q = len(poisson_schedule(t["rate_qps"], args.seconds, args.seed))
    else:
        n_q = t["pool"]
    qs, _ = corpus.queries(n_q, args.seed)
    _warm(r, qs, t, params)
    compiles = _CompileCounter()
    setup_s = time.perf_counter() - T_START
    log(f"[setup] setup_s={setup_s:.3f} index_built={built} queries={n_q}")

    tracer = Tracer(capacity=1 << 20)
    trace_dir = root / "plaidbench" / ".traces" / f"{cell.name}-{args.seed}"
    if args.trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        prof = jax.profiler.trace(str(trace_dir), profiler_options=opts)
    else:
        prof = contextlib.nullcontext()
    n_compiles_before = compiles.n
    with prof:
        with jax.profiler.TraceAnnotation("plaidbench.window"):
            win, extra = _window(cell, r, qs, args.seconds, args.seed, tracer)
    in_window = compiles.n - n_compiles_before
    log(f"[window] seconds={win.seconds:.3f} requests={win.n} answered={int(win.answered.sum())} "
        f"compiles_in_window={in_window} " + " ".join(f"{k}={v}" for k, v in extra.items()))

    stats = [d.memory_stats() or {} for d in devices]
    peak = max(int(s.get("peak_bytes_in_use", 0)) for s in stats)
    srv_spans = tracer.spans()
    _free(r)
    del r

    # ---- the comparison with the reference (untimed) ------------------------
    t_ref = time.perf_counter()
    k = params.k
    n_pool = len(qs)
    qrow = np.arange(win.n) % n_pool
    answered = win.answered
    idx = check.sample(answered, t["check_sample"], args.seed)
    served_s = np.stack([win.scores[i] for i in idx])
    served_p = np.stack([win.pids[i] for i in idx])
    sample_q = qs[qrow[idx]]
    _, exact_p = reference.exhaustive_topk(corpus, sample_q, k)
    ref_s = reference.score_pids(corpus, sample_q, served_p)
    values = {
        "unanswered": int(win.n - answered.sum()),
        "bad_answers": check.bad_answers(win.scores, win.pids, k, corpus.spec.passages),
        "score_gap": check.score_gap(served_s, served_p, ref_s),
    }
    exhaustive_s = time.perf_counter() - t_ref
    pidx = check.sample(answered, t["plaid_sample"], args.seed, stream=4)
    _, plain_p, reached = reference.plaid_topk(
        corpus, corpus_mod.load_inverted_lists(corpus), qs[qrow[pidx]], cfg["search"],
        shards=shards,
    )
    values["plaid_miss"] = check.plaid_miss(np.stack([win.pids[i] for i in pidx]), plain_p)
    ok, checks = check.verdict(values, cfg["limits"])
    recall = check.recall(served_p, exact_p, k)
    log(f"[reference] sample={len(idx)} plaid_sample={len(pidx)} "
        f"seconds={time.perf_counter() - t_ref:.3f} exhaustive_s={exhaustive_s:.3f} recall_k={recall:.6f} "
        f"probed_passages_max={int(reached.max())} shards={shards} "
        f"cap={reference.shard_cap(cfg['search'], corpus.spec.passages, shards)}")

    # ---- metrics -----------------------------------------------------------
    device = {
        "platform": devices[0].platform, "kind": kind, "count": len(jax.devices()),
        "memory_peak_bytes": peak,
    }
    result = {"correct": ok, "attempted": int(win.n), "failed": int(win.n - answered.sum())}
    if args.trace == 0:
        lat = win.latency_s[np.isfinite(win.latency_s)] if win.latency_s is not None else None
        e2e = {
            "setup_s": setup_s,
            "recall_k": recall,
            "qps": float(answered.sum() / win.seconds),
        }
        if lat is not None:
            # a request that failed counts as missing every limit: +inf
            full = np.where(np.isfinite(win.latency_s), win.latency_s, np.inf)
            e2e["p50_ms"] = float(np.percentile(full, 50) * 1e3)
        metrics = {
            m["name"]: {"value": _finite(e2e[m["name"]]), "unit": m["unit"]}
            for m in cell.end_to_end()
        }
        result.update(metrics=metrics, device=device)
    else:
        from plaidbench import xplane

        summary = xplane.reduce(xplane.find_xplane(str(trace_dir)))
        ctx = {
            "trace": summary, "spans": srv_spans, "config": cfg, "traffic": t,
            "params": params, "peaks": peaks, "chips": len(devices),
            "window": win, "qps": float(answered.sum() / win.seconds),
            "mean_len": float(corpus.lens.mean()),
        }
        metrics = {}
        for m in cell.per_layer():
            v = spec_mod.load_reader(root, m["name"])(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        device.update(busy_s=summary.busy_s, window_s=summary.window_s)
        result.update(
            metrics=metrics, device=device,
            breakdown={"device_ops": summary.top_ops(10), "idle_gaps": summary.idle_gaps(10)},
        )
        log(f"[trace] {trace_dir} busy_s={summary.busy_s:.6f} window_s={summary.window_s:.6f}")
    result["checks"] = {n: {kk: _finite(vv) for kk, vv in c.items()} for n, c in checks.items()}
    for n, c in checks.items():
        log(f"check {n}={c['value']!r} limit={c['limit']!r}")
    print(json.dumps(result), flush=True)
    return result


def main(argv=None) -> None:
    run(argv)


if __name__ == "__main__":
    main()
