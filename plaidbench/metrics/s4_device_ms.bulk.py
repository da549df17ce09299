"""Pipeline, stage 4: device time per run of the search program of the
operations it runs under ``plaid.s4`` (the finalists' residual gather,
decompression and exact MaxSim, and the final top-k), in ms
(``plaidbench/stages.py``)."""
from plaidbench import stages


def read(ctx):
    return stages.stage_reading(ctx, "plaid.s4")
