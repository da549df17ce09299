"""Pipeline, stage 1: device time per run of the search program of the
operations it runs under ``plaid.s1`` (the batch's ``C.Q^T`` dot and the
per-token probe top-k over K), in ms (``plaidbench/stages.py``)."""
from plaidbench import stages


def read(ctx):
    return stages.stage_reading(ctx, "plaid.s1")
