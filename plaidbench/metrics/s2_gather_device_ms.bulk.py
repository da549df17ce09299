"""Pipeline, stage-2 CSR gather: device time per run of the search program
of the operations it runs under ``plaid.s2.gather`` (the batch's pool of
candidates and the gather of their codes and token masks from the CSR
arrays), in ms (``plaidbench/stages.py``)."""
from plaidbench import stages


def read(ctx):
    return stages.stage_reading(ctx, "plaid.s2.gather")
