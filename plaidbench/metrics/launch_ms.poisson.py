"""Serving to facade: mean host time of the facade's ``retrieval.launch``
spans that began in the window (from ``search_batch``'s entry until the
engine returns unblocked arrays: request packing, the engine and the
program's dispatch), in ms."""
from plaidbench import stages


def read(ctx):
    d = [s.dur for s in stages.facade_spans(ctx, "retrieval.launch")]
    return 1e3 * sum(d) / len(d) if d else None
