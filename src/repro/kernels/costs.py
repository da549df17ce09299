"""Analytic per-kernel HBM-traffic / flops cost model.

Interpret-mode Pallas (the CI backend) inlines kernels into XLA, so the
HLO-text roofline (``launch.hlo_analysis.analyze``) cannot attribute bytes
to a kernel.  These functions rebuild each kernel's traffic from the SAME
(grid, block shape, index map) triples its ``pallas_call`` uses, via
``hlo_analysis.pallas_block_traffic`` — pure shape arithmetic, identical on
every machine and jax version, which is what makes the per-kernel
``hbm_bytes`` records in BENCH JSON safe to hard-gate in CI
(``benchmarks.bench_diff``).

The two composite stage-3-5 entries compare the tails: both gather their
blocks in XLA and score them with the same stage-4 kernel; the unfused tail
routes stage 2's codes/validity blocks (read + write each), the fused one
gathers code windows by pid and derives validity from the lengths (write
only).  ``tests/test_fused.py`` pins ``fused < unfused``.

Flops count the MXU matmuls only (the unpack/select chains are cheap VPU
integer ops, identical between paths, and would only pad both sides).
"""
from __future__ import annotations

from repro.launch.hlo_analysis import pallas_block_traffic

_F32 = 4
_I32 = 4
_U8 = 1


def _ceil_div(a: int, b: int) -> int:
    return -(-a // b)


def decompress_residuals_cost(
    *, n: int, pd: int, nbits: int, row_block: int = 256
) -> dict:
    """``kernels.decompress.decompress_residuals_pallas``: grid
    (n/row_block,); packed rows stream, the (2^b, 1) weight table stays
    resident across the grid.  No MXU work — the unpack/select chain is
    pure VPU, so flops=0 (consistent with the module policy of counting
    matmuls only)."""
    blocks = _ceil_div(n, row_block)
    vpb = 8 // nbits
    hbm = pallas_block_traffic(
        (blocks,),
        in_specs=[
            (row_block * pd * _U8, lambda i: (i, 0)),  # packed block
            ((2**nbits) * _F32, lambda i: (0, 0)),  # weights (resident)
        ],
        out_specs=[(row_block * pd * vpb * _F32, lambda i: (i, 0))],
    )
    return dict(hbm_bytes=hbm, flops=0.0)


def centroid_interaction_batched_cost(
    *, B: int, nd: int, L: int, K: int, nq: int, doc_block: int = 32
) -> dict:
    """``kernels.maxsim.centroid_interaction_batched_pallas``: grid
    (B, nd/doc_block); the XLA-gathered (doc_block, nq, L) f32 token-score
    blocks stream, q_mask resident per lane.  ``K`` does not enter: the
    score table never reaches the kernel."""
    blocks = _ceil_div(nd, doc_block)
    nd_p = blocks * doc_block
    hbm = pallas_block_traffic(
        (B, blocks),
        in_specs=[
            (doc_block * nq * L * _F32, lambda b, i: (b, i, 0, 0)),  # scores
            (nq * _F32, lambda b, i: (b, 0, 0)),  # q_mask
        ],
        out_specs=[(doc_block * _F32, lambda b, i: (b, i, 0))],
    )
    # masked max + mask-weighted sum: no dot
    flops = 2.0 * B * nd_p * L * nq
    return dict(hbm_bytes=hbm, flops=flops)


def decompress_and_score_batched_cost(
    *,
    B: int,
    nd: int,
    L: int,
    pd: int,
    K: int,
    d: int,
    nq: int,
    nbits: int,
    doc_block: int = 8,
) -> dict:
    """``kernels.decompress.decompress_and_score_batched_pallas``: grid
    (B, nd/doc_block); q tile resident per lane, weights resident across
    the grid; the XLA-gathered f32 centroid rows, packed residuals and
    validity stream.  ``K`` does not enter: the centroid table never
    reaches the kernel."""
    blocks = _ceil_div(nd, doc_block)
    nd_p = blocks * doc_block
    rows = doc_block * L
    hbm = pallas_block_traffic(
        (B, blocks),
        in_specs=[
            (nq * d * _F32, lambda b, i: (b, 0, 0)),  # q lane tile
            (nq * _F32, lambda b, i: (b, 0, 0)),  # q_mask
            (rows * d * _F32, lambda b, i: (b, i, 0)),  # centroid rows
            (rows * pd * _U8, lambda b, i: (b, i, 0)),  # residuals
            (rows * _I32, lambda b, i: (b, i, 0)),  # tok_valid i32
            ((2**nbits) * _F32, lambda b, i: (0, 0)),  # weights
        ],
        out_specs=[(doc_block * _F32, lambda b, i: (b, i, 0))],
    )
    flops = 2.0 * B * nd_p * L * d * nq  # emb @ q.T per candidate token
    return dict(hbm_bytes=hbm, flops=flops)


def _stage4_centroid_gather_bytes(*, B: int, n3: int, L: int, d: int) -> int:
    """The XLA centroid-row gather feeding the stage-4 kernel: read the
    codes block, write the (B, n3, L, d) f32 rows (the rows it reads from
    the centroid table equal the rows it writes)."""
    return B * n3 * L * (_I32 + 2 * d * _F32)


def gather_decompress_maxsim_cost(
    *, B: int, n3: int, L: int, pd: int, K: int, d: int, nq: int, nbits: int
) -> dict:
    """``kernels.fused_score.gather_decompress_maxsim_pallas``: the
    finalists' CSR windows gathered by pid (codes + residuals read once
    from the token arrays and written as blocks; validity computed from
    the lengths, write only), the centroid-row gather, then the stage-4
    kernel."""
    gather_bytes = (
        2 * B * n3 * L * pd * _U8  # residual windows: CSR read + write
        + 2 * B * n3 * L * _I32  # code windows: CSR read + write
        + B * n3 * L * _I32  # validity from lens: write only
    )
    kern = decompress_and_score_batched_cost(
        B=B, nd=n3, L=L, pd=pd, K=K, d=d, nq=nq, nbits=nbits
    )
    return dict(
        hbm_bytes=gather_bytes
        + _stage4_centroid_gather_bytes(B=B, n3=n3, L=L, d=d)
        + kern["hbm_bytes"],
        flops=kern["flops"],
    )


def unfused_stage345_cost(
    *,
    B: int,
    n3: int,
    L: int,
    pd: int,
    K: int,
    d: int,
    nq: int,
    nbits: int,
    doc_block: int = 8,
) -> dict:
    """The stage-3-5 tail fed by stage 2's blocks: the XLA residual gather
    (read the selected CSR bytes, WRITE the routed block), the
    codes/validity take-alongs (read + write each), the centroid-row
    gather, then the stage-4 kernel re-reading what they wrote."""
    gather_bytes = (
        2 * B * n3 * L * pd * _U8  # res_blk: CSR read + routed-block write
        + 2 * B * n3 * L * _I32  # codes4 take_along: read + write
        + 2 * B * n3 * L * _I32  # tok_valid4 take_along (i32 in the kernel)
    )
    kern = decompress_and_score_batched_cost(
        B=B, nd=n3, L=L, pd=pd, K=K, d=d, nq=nq, nbits=nbits,
        doc_block=doc_block,
    )
    return dict(
        hbm_bytes=gather_bytes
        + _stage4_centroid_gather_bytes(B=B, n3=n3, L=L, d=d)
        + kern["hbm_bytes"],
        flops=kern["flops"],
    )


def fused_stage345_cost(
    *, B: int, n3: int, L: int, pd: int, K: int, d: int, nq: int, nbits: int
) -> dict:
    """Fused stage-3-5 tail: the pid-addressed gather + stage-4 kernel."""
    return gather_decompress_maxsim_cost(
        B=B, n3=n3, L=L, pd=pd, K=K, d=d, nq=nq, nbits=nbits
    )


# --------------------------------------------------------------------------
# Host->device transfer model (the tiered storage tier)
# --------------------------------------------------------------------------
def tiered_transfer_cost(
    *, pool_docs: int, slice_tokens: int, pd: int, n3: int, B: int,
    p_cap: int | None = None, t_cap: int | None = None,
) -> dict:
    """PCIe bytes for one tiered batch's candidate-slice pull
    (``core.tiered.TieredEngine._gather_slices`` -> ``jax.device_put``).

    Not a pallas kernel — the quantity is BUS traffic, not HBM traffic —
    but the same shape-arithmetic discipline applies, so the measured
    ``TransferStats`` must equal this model exactly (pinned in
    ``tests/test_tiered.py`` and asserted per-run by
    ``benchmarks.tiered_scale``):

    * ``slice_bytes`` — the exact candidate CSR payload: one packed
      residual row + one i32 code per slice token.  This is the number the
      bench_diff gate holds strictly below the resident payload footprint.
    * ``staged_bytes`` — what actually crosses after pow2 staging padding
      (codes + residuals at ``t_cap``, offsets/lens at ``p_cap``) plus the
      (B, n3) i32 pool-local position map.
    """
    slice_bytes = slice_tokens * (pd + _I32)
    if p_cap is None or t_cap is None:
        return dict(slice_bytes=slice_bytes)
    staged_bytes = (
        t_cap * (_I32 + pd)  # codes + residuals staging arrays
        + (p_cap + 1) * _I32  # pool-local CSR offsets
        + p_cap * _I32  # pool-local lens
        + B * n3 * _I32  # pos_pids map
    )
    return dict(slice_bytes=slice_bytes, staged_bytes=staged_bytes)


def resident_payload_bytes(*, num_tokens: int, pd: int) -> int:
    """HBM the resident engine pins for the token payload — the footprint
    tiering evicts, and the strict upper bound bench_diff enforces on the
    per-batch ``slice_bytes``."""
    return num_tokens * (pd + _I32)


# --------------------------------------------------------------------------
# Kernel <-> cost-record registry (completeness-linted in CI)
# --------------------------------------------------------------------------
#: Every ``pallas_call``-launching function in ``repro.kernels`` maps to the
#: cost function modelling its traffic (the single-query wrappers in
#: ``kernels.ops`` launch the batched kernels at B=1).
#: ``tests/test_obs.py`` AST-scans the kernels package
#: and fails when a new pallas_call site appears in neither table below:
#: a kernel outside the traffic model is a kernel CI cannot gate.
KERNEL_COSTS = {
    "centroid_interaction_batched_pallas": centroid_interaction_batched_cost,
    "decompress_residuals_pallas": decompress_residuals_cost,
    "decompress_and_score_batched_pallas": decompress_and_score_batched_cost,
}

#: Deliberately unmodelled pallas_call sites, each with its reason.  Adding
#: a kernel here is an explicit, reviewed decision — the lint test prints
#: the reason next to the exemption.
UNMODELED_KERNELS = {
    "flash_attention": (
        "pedagogical online-softmax reference (repro.kernels."
        "flash_attention); not launched by the retrieval pipeline, so no "
        "BENCH record exists to gate"
    ),
}
