"""Whole search step: the FLOPs the queries completed require (stage 1's
C.Q^T over all K, plus stage 4's MaxSim over max(ndocs / 4, k) passages at
the corpus's mean length; ``costs.query_flops``) per second, over the
chips' bf16 peak, in %."""
from plaidbench import costs


def read(ctx):
    cfg, p, peaks = ctx["config"], ctx["params"], ctx["peaks"]
    if peaks is None:
        return None
    f = costs.query_flops(
        K=cfg["centroids"], d=cfg["dim"], nq=cfg["q_len"], ndocs=p.ndocs,
        k=p.k, mean_len=ctx["mean_len"],
    )
    return 100.0 * f * ctx["qps"] / (ctx["chips"] * peaks["bf16_flops_per_s"])
