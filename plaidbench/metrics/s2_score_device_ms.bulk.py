"""Pipeline, stage-2 scoring: device time per run of the search program of
the operations it runs under ``plaid.s2.score`` (the t_cs prune mask, the
token-score and keep-mask gathers, the centroid-interaction kernel and the
top-ndocs), in ms (``plaidbench/stages.py``)."""
from plaidbench import stages


def read(ctx):
    return stages.stage_reading(ctx, "plaid.s2.score")
