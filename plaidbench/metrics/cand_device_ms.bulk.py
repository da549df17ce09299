"""Pipeline, candidates: device time per run of the search program of the
operations it runs under ``plaid.cand`` (the IVF walk of the probed
centroids, the candidate union and its cap), in ms
(``plaidbench/stages.py``)."""
from plaidbench import stages


def read(ctx):
    return stages.stage_reading(ctx, "plaid.cand")
