"""Operations and bytes that the search needs, from its shapes alone.

The two kernel models are copied from the program's ``kernels/costs.py``
(``centroid_interaction_batched_cost``, ``decompress_and_score_batched_cost``),
with the block traffic of their ``(grid, BlockSpec)`` written out: every
block is read once, as the kernels' index maps never revisit one.  The step's
FLOPs are those one query requires at the configuration's settings.
"""
from __future__ import annotations

F32 = I32 = 4
U8 = 1


def _ceil_div(a: int, b: int) -> int:
    return -(-a // b)


def interaction_cost(*, B: int, nd: int, L: int, nq: int, doc_block: int = 32) -> dict:
    """Stage 2/3 centroid interaction kernel: the (doc_block, nq, L) f32
    token-score blocks stream in, q_mask per lane, one f32 score per
    document out; a masked max and a mask-weighted sum, no dot."""
    blocks = _ceil_div(nd, doc_block)
    nd_p = blocks * doc_block
    hbm = B * blocks * (doc_block * nq * L * F32 + doc_block * F32) + B * nq * F32
    return {"bytes": float(hbm), "flops": 2.0 * B * nd_p * L * nq}


def stage4_cost(*, B: int, nd: int, L: int, d: int, pd: int, nq: int, nbits: int,
                doc_block: int = 8) -> dict:
    """Stage 4 decompress-and-score kernel: per (lane, block) the gathered
    f32 centroid rows, packed residuals and i32 validity stream in; the
    query tile once per lane, the weight table once; emb @ q.T on the MXU."""
    blocks = _ceil_div(nd, doc_block)
    nd_p = blocks * doc_block
    rows = doc_block * L
    per_block = rows * d * F32 + rows * pd * U8 + rows * I32 + doc_block * F32
    hbm = B * blocks * per_block + B * (nq * d * F32 + nq * F32) + (2**nbits) * F32
    return {"bytes": float(hbm), "flops": 2.0 * B * nd_p * L * d * nq}


def query_flops(*, K: int, d: int, nq: int, ndocs: int, k: int, mean_len: float) -> float:
    """FLOPs one query requires: stage 1's C.Q^T over all K centroids, plus
    stage 4's exact MaxSim over its max(ndocs / 4, k) finalists at the
    corpus's mean passage length."""
    stage1 = 2.0 * K * d * nq
    stage4 = 2.0 * max(ndocs // 4, k) * mean_len * d * nq
    return stage1 + stage4


def roofline_share(cost: dict, seconds: float, peaks: dict) -> tuple[float, str]:
    """Least time the chip could take over the time taken, in %, and which
    of operations or bytes bounds it."""
    t_flops = cost["flops"] / peaks["bf16_flops_per_s"]
    t_bytes = cost["bytes"] / peaks["hbm_bytes_per_s"]
    bound = "bytes" if t_bytes >= t_flops else "flops"
    return 100.0 * max(t_flops, t_bytes) / seconds, bound
