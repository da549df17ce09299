"""Pipeline, stage 3: device time per run of the search program of the
operations it runs under ``plaid.s3`` (the survivors' gathers, the full
centroid interaction and the top max(ndocs / 4, k)), in ms
(``plaidbench/stages.py``)."""
from plaidbench import stages


def read(ctx):
    return stages.stage_reading(ctx, "plaid.s3")
