"""Record the small trace that ``test_plaidbench_xplane.py`` reduces.

    python3 plaidbench/tests/record_trace.py plaidbench/tests/data/small.xplane.pb

Run on the chip: three ``plaid-pallas`` searches of the rehearsal corpus
(B = 4), each inside a ``plaidbench.bulk_call`` span, 50 ms of host sleep
between them, all inside ``plaidbench.window``; the ``.xplane.pb`` is copied
to the path given.  Also prints the trace's planes and lines, and the names
and stats of the device's operations, for a look by hand.
"""
from __future__ import annotations

import pathlib
import shutil
import sys
import tempfile
import time

HERE = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent))
import run  # noqa: E402,F401  (puts the checkout and src/ on the path)


def main(dest: str) -> None:
    import json

    import jax
    import jax.profiler

    from plaidbench import corpus as corpus_mod, xplane
    from repro import compile_cache
    from repro.retrieval import SearchParams

    compile_cache.configure()
    cfg = json.load(open(HERE / "data" / "rehearsal.k10.json"))
    params = SearchParams(**cfg["search"])
    corpus = corpus_mod.Corpus(corpus_mod.CorpusSpec.from_config(cfg))
    r, _ = corpus_mod.load_or_build(corpus, "plaid-pallas", params, log=print)
    qs, _ = corpus.queries(4, 0)
    for _ in range(2):
        r.search_batch(qs)
    with tempfile.TemporaryDirectory() as tmp:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        with jax.profiler.trace(tmp, profiler_options=opts):
            with jax.profiler.TraceAnnotation("plaidbench.window"):
                for _ in range(3):
                    with jax.profiler.TraceAnnotation("plaidbench.bulk_call"):
                        r.search_batch(qs)
                    time.sleep(0.05)
        path = xplane.find_xplane(tmp)
        shutil.copy(path, dest)
    from jax.profiler import ProfileData

    data = ProfileData.from_file(dest)
    for plane in data.planes:
        print("PLANE", plane.name, [(ln.name, len(list(ln.events))) for ln in plane.lines])
        if plane.name.startswith("/device:TPU:0"):
            for ln in plane.lines:
                for e in list(ln.events)[:400]:
                    stats = {k: str(v)[:80] for k, v in e.stats}
                    print("  ", ln.name, "|", e.name, e.start_ns, e.duration_ns, stats)
    s = xplane.reduce(dest)
    print("busy_s", s.busy_s, "window_s", s.window_s, "top", s.top_ops(10), "gaps", s.idle_gaps(5))


if __name__ == "__main__":
    main(sys.argv[1])
