"""Serving to facade: mean host time of one dispatched batch, from the call
into the retriever to its results being ready (the program's
``serve.dispatch`` spans, ended by ``block_until_ready``), in ms."""


def read(ctx):
    d = [s.dur for s in ctx["spans"] if s.name == "serve.dispatch"]
    return 1e3 * sum(d) / len(d) if d else None
