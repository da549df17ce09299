"""Serving layer: mean time a request waited in ``BatchingServer``'s queue,
from submit to its batch's dispatch (the program's ``serve.queue_wait``
spans), in ms."""


def read(ctx):
    d = [s.dur for s in ctx["spans"] if s.name == "serve.queue_wait"]
    return 1e3 * sum(d) / len(d) if d else None
