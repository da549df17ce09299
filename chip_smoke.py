#!/usr/bin/env python3
"""Smoke run of the PLAID search path on a TPU, through its public entry points.

One process drives the main path once, in this order:

1. encoder: the ColBERTv2 encoder at full width (``configs.colbertv2``:
   12 layers, d_model 768, out_dim 128, bf16) with seeded random weights
   encodes a batch of query token ids under ``jax.jit``;
2. index: ``retrieval.build`` runs the streaming two-pass builder over a
   seeded synthetic corpus (``data.synthetic.CorpusStream``: d = 128,
   nbits = 2, heavy-tailed passage lengths averaging ~64 tokens, K from
   ``core.kmeans.num_centroids_for``), fed chunk by chunk;
3. search: ``backend="plaid"`` and ``backend="plaid-pallas"`` answer the
   encoded queries and queries drawn from corpus passages; the two return
   identical pids, the source passage is recovered at rank 1, and the
   ``plaid-pallas`` program holds compiled Mosaic kernels;
4. serving: a ``BatchingServer`` over ``plaid-pallas`` answers concurrent
   submits at several batch sizes, array-identical to direct search, and
   drains on shutdown.

``--chips 4`` runs only the document-sharded path instead: ``plaid-sharded``
over a 4-device mesh against one-device ``plaid`` on the same index.

Each phase prints one line.  The last line of stdout is one JSON object
``{"ok": true, "device": {...}}``.  Any failed check raises, and the exit
code is non-zero.  Without a TPU, or with ``REPRO_FORCE_INTERPRET`` set,
the script refuses to run; ``--rehearse`` runs it on the CPU at a tiny size
with the Pallas interpreter (a rehearsal, not a measurement).

    python3 chip_smoke.py                        # one chip, 1M passages
    python3 chip_smoke.py --chips 4              # plaid-sharded, four chips
    JAX_PLATFORMS=cpu python3 chip_smoke.py --rehearse
"""
from __future__ import annotations

import argparse
import json
import os
import pathlib
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))

Q_LEN = 32  # ColBERTv2 query_maxlen
DIM = 128
NBITS = 2
#: Success@1 of corpus-drawn queries that both backends must reach.
SUCCESS_FLOOR = 0.9
#: Candidate cap of the --chips 4 comparison; both paths keep cap // 4
#: finalists, above the largest candidate set at 1M passages (6,792).
SHARDED_CAP = 32768


def _log(phase: str, **fields) -> None:
    body = " ".join(f"{k}={v}" for k, v in fields.items())
    print(f"[{phase}] {body}", flush=True)


def _bytes_in_use(devices) -> list[int] | None:
    """Per-device bytes in use, or None where the backend reports none."""
    stats = [d.memory_stats() for d in devices]
    if any(s is None for s in stats):
        return None
    return [int(s["bytes_in_use"]) for s in stats]


def _check_device(args):
    """The devices to run on; refuses a CPU fallback outside rehearsals."""
    import jax

    devices = jax.devices()
    if not args.rehearse:
        if devices[0].platform != "tpu":
            sys.exit(
                f"chip_smoke: no TPU visible (platform "
                f"{devices[0].platform!r}); --rehearse runs the CPU rehearsal"
            )
        if os.environ.get("REPRO_FORCE_INTERPRET") is not None:
            sys.exit(
                "chip_smoke: REPRO_FORCE_INTERPRET is set; the smoke run "
                "must compile its kernels with Mosaic"
            )
    if len(devices) < args.chips:
        sys.exit(
            f"chip_smoke: --chips {args.chips} needs {args.chips} devices, "
            f"{len(devices)} visible"
        )
    return devices[: args.chips]


def _build_index(args, n_devices: int):
    """Streaming build over the seeded synthetic corpus; returns
    (index, stream, seconds)."""
    from repro import retrieval
    from repro.build.chunks import ChunkStream
    from repro.data.synthetic import CorpusStream

    stream = CorpusStream(
        args.docs, DIM, chunk_tokens=args.chunk_tokens, seed=args.seed
    )
    t0 = time.perf_counter()
    r = retrieval.build(
        ChunkStream(factory=stream.chunks, encode_fn=stream.encode),
        backend="plaid",
        index=dict(nbits=NBITS, seed=args.seed, n_devices=n_devices),
    )
    return r.index, stream, time.perf_counter() - t0


def _index_bytes(index) -> int:
    import jax

    return sum(x.nbytes for x in jax.tree.leaves(index))


def _corpus_queries(stream, n: int, seed: int):
    """``n`` queries drawn from passages of the first and the middle chunk,
    with their source pids.  (A candidate set over ``candidate_cap`` keeps
    its lowest pids, so the corpus's last passages are the first a full cap
    drops: the search phase reports how many queries reached the cap.)"""
    import numpy as np

    from repro.data.synthetic import queries_from_docs

    chunks = sorted({0, len(stream.chunk_lens) // 2})
    qs, golds = [], []
    for i, c in enumerate(chunks):
        m = n // len(chunks) + (i < n % len(chunks))
        q, g = queries_from_docs(stream.docs(c), m, q_len=Q_LEN, seed=seed + c)
        qs.append(q)
        golds.append(g + stream.chunk_pid0[c])
    return np.concatenate(qs), np.concatenate(golds)


# --------------------------------------------------------------------------
# one chip: encoder -> index -> search -> serving
# --------------------------------------------------------------------------
def phase_encoder(args):
    import dataclasses

    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs import colbertv2
    from repro.models import colbert

    if args.rehearse:
        cfg = dataclasses.replace(colbertv2.reduced_config(), out_dim=DIM)
    else:
        cfg = colbertv2.full_config()
    bb = cfg.backbone
    k_p, k_t = jax.random.split(jax.random.PRNGKey(args.seed))
    params = colbert.init_params(k_p, cfg)
    tokens = jax.random.randint(k_t, (args.batch, Q_LEN), 0, bb.vocab)
    t0 = time.perf_counter()
    encode = jax.jit(lambda p, t: colbert.encode(p, cfg, t)).lower(
        params, tokens
    ).compile()
    compile_s = time.perf_counter() - t0
    q = np.asarray(encode(params, tokens))
    if q.shape != (args.batch, Q_LEN, cfg.out_dim) or cfg.out_dim != DIM:
        raise AssertionError(f"encoder output shape {q.shape}")
    if not np.isfinite(q).all():
        raise AssertionError("encoder output is not finite")
    norms = np.linalg.norm(q, axis=-1)
    if not np.allclose(norms, 1.0, atol=1e-3):
        raise AssertionError(f"token norms off unit: {norms.min()}..{norms.max()}")
    if np.allclose(q[0], q[1]):
        raise AssertionError("distinct queries encoded identically")
    _log(
        "encoder", layers=bb.n_layers, d_model=bb.d_model,
        out_dim=cfg.out_dim, dtype=jnp.dtype(bb.dtype).name, q_len=Q_LEN,
        batch=args.batch, compile_s=f"{compile_s:.2f}",
        checks="shape,finite,unit_norm",
    )
    return jnp.asarray(q)


def phase_index(args, device):
    index, stream, build_s = _build_index(args, n_devices=1)
    if index.num_passages != stream.n_docs or index.num_passages < args.docs:
        raise AssertionError(
            f"index holds {index.num_passages} passages, corpus "
            f"{stream.n_docs}, asked for {args.docs}"
        )
    if (index.dim, index.nbits) != (DIM, NBITS):
        raise AssertionError(f"index widths d={index.dim} nbits={index.nbits}")
    in_use = _bytes_in_use([device])
    _log(
        "index", passages=index.num_passages, tokens=index.num_tokens,
        mean_len=f"{index.num_tokens / index.num_passages:.1f}",
        K=index.num_centroids, d=index.dim, nbits=index.nbits,
        doc_maxlen=index.doc_maxlen, ivf_list_cap=index.ivf_list_cap,
        index_bytes=_index_bytes(index),
        device_bytes_in_use=in_use[0] if in_use else "n/a",
        build_s=f"{build_s:.1f}",
    )
    return index, stream


def _run_batches(r, qs, batch: int, t_cs: float, diag: bool = False):
    """search_batch over ``qs`` in fixed-size batches with a per-lane t_cs
    vector (the program the serving tier dispatches); returns (scores,
    pids, first-call seconds, median later-call seconds, stage-1
    candidate counts or None)."""
    import numpy as np

    t = np.full(batch, t_cs, np.float32)
    scores, pids, times, cands = [], [], [], []
    for i in range(0, len(qs), batch):
        t0 = time.perf_counter()
        res = r.search_batch(qs[i : i + batch], t_cs=t, with_diagnostics=diag)
        scores.append(np.asarray(res.scores))
        pids.append(np.asarray(res.pids))
        times.append(time.perf_counter() - t0)
        if diag:
            cands.append(res.diagnostics["stage1_candidates"])
    steady = times[1:] or times
    return (
        np.concatenate(scores), np.concatenate(pids), times[0],
        sorted(steady)[len(steady) // 2],
        np.concatenate(cands) if diag else None,
    )


def phase_search(args, index, stream, q_enc):
    import dataclasses

    import numpy as np

    from repro import retrieval
    from repro.core import pipeline, plaid
    from repro.kernels import dispatch
    from repro.retrieval.backends import to_engine_params

    params = retrieval.params_for_k(10)
    r_ref = retrieval.from_index(index, backend="plaid", params=params)
    r_pal = retrieval.from_index(index, backend="plaid-pallas", params=params)

    q_doc, gold = _corpus_queries(stream, args.queries, args.seed + 1)
    qs = np.concatenate([np.asarray(q_enc), q_doc])
    out = {
        "plaid": _run_batches(r_ref, qs, args.batch, params.t_cs, diag=True),
        "plaid-pallas": _run_batches(r_pal, qs, args.batch, params.t_cs),
    }
    for name, (_, _, first, steady, _) in out.items():
        _log(
            "search", backend=name, queries=len(qs), batch=args.batch,
            first_call_s=f"{first:.2f}", steady_call_s=f"{steady:.4f}",
        )
    s_ref, p_ref, *_, cands = out["plaid"]
    s_pal, p_pal = out["plaid-pallas"][:2]
    cap = min(params.candidate_cap, index.num_passages)
    _log(
        "search", stage1_candidates_max=int(cands.max()), candidate_cap=cap,
        queries_at_cap=int((cands >= cap).sum()),
    )
    if not np.array_equal(p_ref, p_pal):
        bad = np.flatnonzero((p_ref != p_pal).any(axis=1))
        raise AssertionError(f"plaid and plaid-pallas pids differ on queries {bad}")
    if not (np.isfinite(s_ref).all() and np.isfinite(s_pal).all()):
        raise AssertionError("non-finite scores")
    score_diff = float(np.abs(s_ref - s_pal).max())
    if score_diff > 1e-3:
        raise AssertionError(f"plaid vs plaid-pallas scores differ by {score_diff}")
    n_enc = len(q_enc)
    success = float((p_ref[n_enc:, 0] == gold).mean())
    if success < SUCCESS_FLOOR:
        raise AssertionError(f"success@1 {success} below floor {SUCCESS_FLOOR}")

    # the plaid-pallas program: Mosaic kernels, not the interpreter
    interpret = dispatch.default_interpret()
    p = dataclasses.replace(
        plaid.clamp_params(to_engine_params(params, "pallas"), index.num_passages),
        t_cs=0.0,
    )
    hlo = pipeline.run_pipeline_jit.lower(
        index, qs[: args.batch], np.ones((args.batch, Q_LEN), np.float32),
        np.full(args.batch, params.t_cs, np.float32), params=p,
    ).as_text()
    kernels = hlo.count("tpu_custom_call")
    if not args.rehearse and (interpret or kernels == 0):
        raise AssertionError(
            f"plaid-pallas is not on Mosaic: interpret={interpret}, "
            f"tpu_custom_call x{kernels}"
        )
    _log(
        "search", pids_identical=True, max_score_diff=f"{score_diff:.2e}",
        success_at_1=f"{success:.3f}", success_floor=SUCCESS_FLOOR,
        interpret=interpret, tpu_custom_call=kernels,
    )
    return r_pal, qs, (s_pal, p_pal)


def phase_serving(args, r_pal, qs, direct):
    import numpy as np

    from repro.serving import BatchingServer

    s_dir, p_dir = direct
    srv = BatchingServer(
        r_pal, batch_size=args.batch, max_wait_ms=200.0, cache_size=None
    )
    # one burst per size, each coalescing into its own bucket; the last
    # burst is still queued or in flight when shutdown(drain=True) starts
    sizes = sorted({args.batch, max(1, args.batch // 2 - 1), 1}, reverse=True)
    submitted = []
    try:
        for n in sizes:
            for _, f in submitted:
                f.get(timeout=900)
            submitted += [(i, srv.submit(qs[i])) for i in range(n)]
    finally:
        srv.shutdown(drain=True, timeout=900)
    for i, f in submitted:
        if not f.done():
            raise AssertionError(f"query {i} unanswered after a drained shutdown")
        res = f.get(timeout=0)
        if not (
            np.array_equal(res.pids, p_dir[i])
            and np.array_equal(res.scores, s_dir[i])
        ):
            raise AssertionError(f"served query {i} differs from direct search")
    st = srv.stats()
    if st["completed"] != len(submitted) or st["errors"]:
        raise AssertionError(f"server stats {st}")
    _log(
        "serving", bursts=sizes, requests=len(submitted),
        buckets=st["buckets"], identical_to_direct=True, drained=True,
    )


# --------------------------------------------------------------------------
# four chips: plaid-sharded vs one-device plaid
# --------------------------------------------------------------------------
def phase_sharded(args, devices):
    import numpy as np

    from repro import retrieval

    index, stream, build_s = _build_index(args, n_devices=len(devices))
    index_bytes = _index_bytes(index)
    _log(
        "index", passages=index.num_passages, tokens=index.num_tokens,
        K=index.num_centroids, d=index.dim, nbits=index.nbits,
        doc_maxlen=index.doc_maxlen, index_bytes=index_bytes,
        build_devices=len(devices), build_s=f"{build_s:.1f}",
    )
    # Caps at which neither path truncates: both keep cap // 4 finalists
    # for exact scoring, and the check below asserts that no query has
    # more candidates, so the doc-partitioned search must equal the
    # global one.
    cap = min(SHARDED_CAP, index.num_passages)
    params = retrieval.SearchParams(
        k=10, nprobe=1, t_cs=0.5, ndocs=cap, candidate_cap=cap
    )
    before = _bytes_in_use(devices)
    r_sh = retrieval.from_index(
        index, backend="plaid-sharded", params=params, n_shards=len(devices)
    )
    after = _bytes_in_use(devices)
    r_one = retrieval.from_index(index, backend="plaid", params=params)

    q_doc, gold = _corpus_queries(stream, args.queries, args.seed + 1)
    batch = args.sharded_batch
    got_s, got_p, want_s, want_p, cands = [], [], [], [], []
    for i in range(0, len(q_doc), batch):
        qb = q_doc[i : i + batch]
        one = r_one.search_batch(qb, with_diagnostics=True)
        sh = r_sh.search_batch(qb)
        cands.append(one.diagnostics["stage1_candidates"])
        want_s.append(np.asarray(one.scores))
        want_p.append(np.asarray(one.pids))
        got_s.append(np.asarray(sh.scores))
        got_p.append(np.asarray(sh.pids))
    got_s, got_p = np.concatenate(got_s), np.concatenate(got_p)
    want_s, want_p = np.concatenate(want_s), np.concatenate(want_p)
    n3 = max(cap // 4, params.k)  # the one-device path's stage-3 keep
    if int(np.max(cands)) > n3:
        raise AssertionError(
            f"{int(np.max(cands))} candidates exceed the untruncated bound "
            f"{n3}; raise SHARDED_CAP"
        )
    np.testing.assert_array_equal(got_p, want_p)
    np.testing.assert_allclose(got_s, want_s, atol=1e-4)
    success = float((got_p[:, 0] == gold).mean())
    if success < SUCCESS_FLOOR:
        raise AssertionError(f"success@1 {success} below floor {SUCCESS_FLOOR}")
    per_dev = "n/a"
    if before is not None:
        per_dev = [a - b for a, b in zip(after, before)]
        if max(per_dev) > index_bytes / 2:
            raise AssertionError(
                f"a device took {max(per_dev)} bytes of a {index_bytes}-byte "
                "index: the shards are not spread over the mesh"
            )
    _log(
        "sharded", shards=len(devices), queries=len(q_doc), batch=batch,
        candidate_cap=cap, max_candidates=int(np.max(cands)),
        pids_identical=True, success_at_1=f"{success:.3f}",
        index_bytes=index_bytes, shard_bytes_per_device=per_dev,
        bytes_in_use_per_device=after or "n/a",
    )


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only plaid-sharded vs one-device plaid")
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU rehearsal at a tiny size (Pallas interpreter, "
                         "reduced encoder depth and width)")
    ap.add_argument("--docs", type=int, default=None,
                    help="passages in the corpus (default 1,000,000; "
                         "2,000 with --rehearse)")
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--queries", type=int, default=64,
                    help="queries drawn from corpus passages")
    ap.add_argument("--chunk-tokens", type=int, default=None,
                    help="tokens per build chunk (default 2^18; 2^13 with "
                         "--rehearse)")
    ap.add_argument("--sharded-batch", type=int, default=2)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    if args.queries % args.batch or args.queries % args.sharded_batch:
        ap.error("--queries must be a multiple of both batch sizes")
    if args.docs is None:
        args.docs = 2000 if args.rehearse else 1_000_000
    if args.chunk_tokens is None:
        args.chunk_tokens = 1 << 13 if args.rehearse else 1 << 18

    from repro import compile_cache

    cache_dir = compile_cache.configure()
    import jax

    devices = _check_device(args)
    d0 = devices[0]
    _log(
        "devices", platform=d0.platform, kind=repr(d0.device_kind),
        count=len(jax.devices()), using=len(devices), jax=jax.__version__,
        compile_cache=cache_dir,
    )
    if args.chips == 4:
        phase_sharded(args, devices)
    else:
        q_enc = phase_encoder(args)
        index, stream = phase_index(args, d0)
        r_pal, qs, direct = phase_search(args, index, stream, q_enc)
        phase_serving(args, r_pal, qs, direct)
    print(json.dumps({
        "ok": True,
        "device": {
            "platform": jax.devices()[0].platform,
            "kind": jax.devices()[0].device_kind,
            "count": len(jax.devices()),
        },
    }), flush=True)


if __name__ == "__main__":
    main()
