"""Kernels: the stage 2/3 centroid-interaction Pallas kernel
(``kernels/maxsim.py``) against its roofline, in %: the least time its
bytes and operations need at the chip's peaks (``costs.interaction_cost``
for the two calls of each search: stage 2 over the candidate cap, stage 3
over the stage-2 survivors) over its summed device time."""
from plaidbench import costs, xplane

#: the kernel's events: ``tpu_custom_call``s named after ``ops.centroid_interaction_batched``
KERNEL = xplane.kernel("centroid_interaction_batched")


def read(ctx):
    tr, p, cfg, peaks = ctx["trace"], ctx["params"], ctx["config"], ctx["peaks"]
    seconds = tr.op_seconds(KERNEL)
    searches = len(tr.module_events(lambda n: "run_pipeline" in n))
    if peaks is None or seconds <= 0 or searches == 0:
        return None
    B = ctx["traffic"]["batch"]
    cap = min(p.candidate_cap, cfg["passages"])
    n2 = min(p.ndocs, cap)
    per_search = [
        costs.interaction_cost(B=B, nd=nd, L=cfg["doc_maxlen"], nq=cfg["q_len"])
        for nd in (cap, n2)
    ]
    total = {
        key: searches * sum(c[key] for c in per_search) for key in ("bytes", "flops")
    }
    share, _ = costs.roofline_share(total, seconds, peaks)
    return share
