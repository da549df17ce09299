"""Kernels: the stage-4 decompress-and-score Pallas kernel
(``kernels/decompress.py``) against its roofline, in %: the least time its
bytes and operations need at the chip's peaks (``costs.stage4_cost`` over
the max(ndocs / 4, k) finalists of each search) over its summed device
time."""
from plaidbench import costs, xplane

#: the kernel's events: ``tpu_custom_call``s named after ``ops.decompress_and_score_batched``
KERNEL = xplane.kernel("decompress_and_score_batched")


def read(ctx):
    tr, p, cfg, peaks = ctx["trace"], ctx["params"], ctx["config"], ctx["peaks"]
    seconds = tr.op_seconds(KERNEL)
    searches = len(tr.module_events(lambda n: "run_pipeline" in n))
    if peaks is None or seconds <= 0 or searches == 0:
        return None
    B = ctx["traffic"]["batch"]
    cap = min(p.candidate_cap, cfg["passages"])
    n3 = min(max(p.ndocs // 4, p.k), min(p.ndocs, cap))
    c = costs.stage4_cost(
        B=B, nd=n3, L=cfg["doc_maxlen"], d=cfg["dim"],
        pd=cfg["dim"] * cfg["nbits"] // 8, nq=cfg["q_len"], nbits=cfg["nbits"],
    )
    total = {key: searches * c[key] for key in ("bytes", "flops")}
    share, _ = costs.roofline_share(total, seconds, peaks)
    return share
