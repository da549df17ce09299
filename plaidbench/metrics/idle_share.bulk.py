"""Device: the share of the traced window in which no operation ran on the
chip (1 - union of the ``XLA Ops`` intervals / window)."""


def read(ctx):
    tr = ctx["trace"]
    return tr.idle_share if tr.chips else None
