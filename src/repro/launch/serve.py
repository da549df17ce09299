"""Serving driver: build a retrieval index over a synthetic corpus and serve
batched requests through the ``repro.retrieval`` facade.

``python -m repro.launch.serve --docs 20000 --queries 256 --k 10
[--backend plaid|plaid-pallas|plaid-sharded|vanilla|live|live-pallas]
[--compare-vanilla]
[--sweep-t-cs]`` prints latency percentiles, (optionally) the speedup +
agreement vs. the vanilla ColBERTv2 baseline (the paper's Table 3 protocol
at laptop scale), and (optionally) a dynamic ``t_cs`` sweep that reuses one
compiled program for every threshold.
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import compile_cache, retrieval
from repro.core import index as index_mod
from repro.data import synthetic as syn


def percentile_ms(times, p):
    return float(np.percentile(np.asarray(times) * 1e3, p))


def _timed_sweep(searcher, qs, batch):
    times, all_pids = [], []
    for i in range(0, qs.shape[0], batch):
        chunk = qs[i : i + batch]
        t0 = time.perf_counter()
        res = searcher.search_batch(chunk)
        jax.block_until_ready(res.pids)
        times.append((time.perf_counter() - t0) / len(chunk))
        all_pids.append(np.asarray(res.pids))
    return times, np.concatenate(all_pids)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--docs", type=int, default=20000)
    ap.add_argument("--queries", type=int, default=256)
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--k", type=int, default=10)
    ap.add_argument("--dim", type=int, default=128)
    ap.add_argument("--nbits", type=int, default=2)
    ap.add_argument(
        "--backend", default="plaid", choices=retrieval.list_backends()
    )
    ap.add_argument("--pallas", action="store_true",
                    help='shorthand for --backend plaid-pallas')
    ap.add_argument("--compare-vanilla", action="store_true")
    ap.add_argument("--sweep-t-cs", action="store_true",
                    help="sweep the pruning threshold without recompiling")
    args = ap.parse_args()
    compile_cache.configure()
    backend = "plaid-pallas" if args.pallas else args.backend

    print(f"building corpus: {args.docs} docs ...")
    docs, _ = syn.embedding_corpus(args.docs, dim=args.dim)
    t0 = time.perf_counter()
    index = index_mod.build_index(docs, nbits=args.nbits)
    jax.block_until_ready(index.centroids)
    print(
        f"index: {index.num_passages} docs / {index.num_tokens} tokens / "
        f"{index.num_centroids} centroids ({time.perf_counter() - t0:.1f}s)"
    )

    qs, gold = syn.queries_from_docs(docs, args.queries)
    qs = jnp.asarray(qs)

    searcher = retrieval.from_index(
        index, backend=backend, params=retrieval.params_for_k(args.k)
    )

    # warmup (compile)
    jax.block_until_ready(searcher.search_batch(qs[: args.batch]).pids)
    times, pids = _timed_sweep(searcher, qs, args.batch)
    hits = int((pids[:, 0] == gold).sum())
    print(
        f"{backend}  k={args.k}: mean {np.mean(times)*1e3:.2f} ms/q  "
        f"p50 {percentile_ms(times, 50):.2f}  p99 {percentile_ms(times, 99):.2f}  "
        f"success@1 {hits / args.queries:.3f}"
    )

    if args.sweep_t_cs:
        if "t_cs" not in searcher.describe()["dynamic_fields"]:
            print(f"  ({backend} has no dynamic t_cs; skipping sweep)")
        else:
            traces0 = searcher.describe()["compile"]["trace_count"]
            for t_cs in (0.3, 0.4, 0.5, 0.6):
                res = searcher.search_batch(qs[: args.batch], t_cs=t_cs)
                s1 = float(
                    (np.asarray(res.pids)[:, 0] == gold[: args.batch]).mean()
                )
                print(f"  t_cs={t_cs:.2f}: success@1 {s1:.3f}  "
                      f"{res.latency_ms / args.batch:.2f} ms/q")
            traces1 = searcher.describe()["compile"]["trace_count"]
            print(f"  sweep recompiles: {traces1 - traces0} "
                  "(static caps unchanged)")

    if args.compare_vanilla:
        vs = retrieval.from_index(
            index,
            backend="vanilla",
            params=retrieval.SearchParams(
                k=args.k, nprobe=4, candidate_cap=2**13, ndocs=4096
            ),
        )
        jax.block_until_ready(vs.search_batch(qs[: args.batch]).pids)
        vt, v_pids = _timed_sweep(vs, qs, args.batch)
        vhits = int((v_pids[:, 0] == gold).sum())
        print(
            f"vanilla k={args.k}: mean {np.mean(vt)*1e3:.2f} ms/q  "
            f"success@1 {vhits / args.queries:.3f}  "
            f"-> {backend} speedup {np.mean(vt) / np.mean(times):.1f}x"
        )


if __name__ == "__main__":
    main()
