"""Tiered beyond-HBM index: device-resident funnel, host-resident payloads.

PLAID's candidate funnel touches a tiny fraction of the token payload per
query (stage 4 rescores ``B*n3`` passages out of millions), yet the
resident engine keeps EVERY packed residual byte in device memory — the
binding constraint far below paper scale (140M passages).  This module
splits the index across a memory tier boundary:

    device tier (hot, O(corpus) but small)     host tier (cold, dominant)
    ------------------------------------       --------------------------
    centroids / centroids_q / scale            residuals  (Nt, pd) u8 mmap
    codes            (Nt,)  i32                codes      (Nt,)  i32 mmap
    doc_offsets / doc_lens (CSR)               tok_pid / eivf_eids (never
    ivf_* centroid->pid CSR                      loaded at all)
    codec tables (cutoffs / weights)

and runs search as a TWO-PHASE pipeline over the ``core.pipeline`` split:

    phase A (device jit)   stages 1-3 — pick (B, n3) finalist pids
         │  final_pids syncs to host (the one device->host hop)
    slice gather (host)    finalists dedup into a sorted pool; the pool's
         │                 CSR slices are copied from the mmap into a
         │                 reusable pinned staging buffer (double-buffered
         │                 so batch N+1's fill overlaps batch N's copy)
    jax.device_put         ONLY the candidate slices cross the PCIe bus —
         │                 measured per batch, gated in CI (bench_diff)
    phase B (device jit)   stage 4 on the compacted slice arrays + top-k

Phase B rebuilds a pool-local :class:`PlaidIndex` view over the compacted
arrays and reuses ``exact_stage4_impl`` verbatim — same bytes, same ops,
same order — so scores and ranks are BITWISE identical to the resident
engine (``tests/test_tiered.py`` pins this across ref/pallas ×
fused/unfused × partition grids).  Compacted shapes are pow2-bucketed
(``exec.segments.pow2_bucket``), so phase B compiles O(log corpus) times,
not per batch.
"""
from __future__ import annotations

import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import pipeline as pl
from repro.core import plaid
from repro.core.index import PlaidIndex


class TieredBudgetError(ValueError):
    """The device tier does not fit the configured device-memory budget."""


_N_TRACES_A = 0
_N_TRACES_B = 0


def trace_counts() -> tuple[int, int]:
    """(phase A, phase B) trace counts — the tiered zero-retrace guard."""
    return _N_TRACES_A, _N_TRACES_B


# --------------------------------------------------------------------------
# The tiered index: a payload-stripped device PlaidIndex + host mmaps
# --------------------------------------------------------------------------
def strip_payload(index: PlaidIndex) -> PlaidIndex:
    """Device-tier view: O(Nt) payload arrays replaced by placeholders.

    ``codes`` stays (stages 2-3 run centroid interaction over candidate
    codes on device); ``residuals`` / ``tok_pid`` / ``eivf_eids`` shrink to
    1-row placeholders — stages 1-3 never read them, and phase B gets the
    real bytes through the compacted slice arrays.
    """
    pd = index.residuals.shape[1]
    z = jnp.zeros((1,), jnp.int32)
    return dataclasses.replace(
        index,
        residuals=jnp.zeros((1, pd), jnp.uint8),
        tok_pid=z,
        eivf_eids=z,
    )


@dataclasses.dataclass
class TieredIndex:
    """Device tier + host-resident payload arrays (usually ``np.memmap``)."""

    device: PlaidIndex  # payload-stripped (see strip_payload)
    host_codes: np.ndarray  # (Nt,) i32
    host_residuals: np.ndarray  # (Nt, pd) u8
    host_doc_offsets: np.ndarray  # (Nd+1,) i32
    host_doc_lens: np.ndarray  # (Nd,) i32

    @property
    def num_passages(self) -> int:
        return int(self.host_doc_lens.shape[0])

    @property
    def num_tokens(self) -> int:
        return int(self.host_codes.shape[0])

    @property
    def payload_itemsize(self) -> int:
        """Bytes per token crossing the bus: packed residual + i32 code."""
        return int(self.host_residuals.shape[1]) + 4

    def device_nbytes(self) -> int:
        """Bytes the device tier pins in HBM (the budgeted quantity)."""
        return sum(
            int(np.asarray(getattr(self.device, f.name)).nbytes)
            for f in dataclasses.fields(PlaidIndex)
            if not f.metadata.get("static")
        )

    def resident_payload_nbytes(self) -> int:
        """Bytes the RESIDENT engine would pin for the token payload —
        the footprint tiering evicts (and the bench_diff upper bound)."""
        return self.num_tokens * self.payload_itemsize

    def resident_nbytes(self) -> int:
        """Total HBM the RESIDENT engine pins for this corpus: the device
        tier plus every O(Nt) array tiering strips (packed residuals and
        the ``tok_pid`` / ``eivf_eids`` side tables, minus their 1-row
        placeholders).  ``resident_nbytes / device_nbytes`` is the
        beyond-HBM scale factor the tiered_scale benchmark reports."""
        pd = int(self.host_residuals.shape[1])
        placeholders = pd + 4 + 4  # the three 1-row stand-ins
        return (
            self.device_nbytes()
            - placeholders
            + self.num_tokens * (pd + 4 + 4)  # residuals, tok_pid, eivf
        )


def tiered_from_index(index: PlaidIndex) -> TieredIndex:
    """Demote a resident index: payloads to host, funnel state on device."""
    return TieredIndex(
        device=strip_payload(index),
        host_codes=np.asarray(index.codes, np.int32),
        host_residuals=np.asarray(index.residuals, np.uint8),
        host_doc_offsets=np.asarray(index.doc_offsets, np.int32),
        host_doc_lens=np.asarray(index.doc_lens, np.int32),
    )


# --------------------------------------------------------------------------
# Phase A / phase B compiled entry points
# --------------------------------------------------------------------------
def _phase_a_impl(
    index, qs, q_masks, t_cs, *, params, funnel=False, keep_blocks=True,
    interpret=None, alive=None,
):
    global _N_TRACES_A
    _N_TRACES_A += 1
    return pl.select_finalists_impl(
        index, qs, q_masks, t_cs, params=params, funnel=funnel,
        interpret=interpret, alive=alive, keep_blocks=keep_blocks,
    )


_phase_a_jit = jax.jit(
    _phase_a_impl,
    static_argnames=("params", "funnel", "keep_blocks", "interpret"),
)


def _phase_b_impl(
    qs,  # (B, nq, d)
    q_masks,  # (B, nq)
    final_pids,  # (B, n3) GLOBAL pids (-1 pad) — output identity
    pos_pids,  # (B, n3) pool-LOCAL positions (-1 pad) — gather identity
    codes4,  # (B, n3, L) | None (fused)
    tok_valid4,  # (B, n3, L) | None (fused)
    codes_c,  # (T_cap,) i32 compacted slice codes
    res_c,  # (T_cap, pd) u8 compacted slice residuals
    offs_c,  # (P_cap+1,) i32 pool-local CSR offsets
    lens_c,  # (P_cap,) i32
    centroids,
    centroids_q,
    centroids_scale,
    cutoffs,
    weights,
    *,
    params,
    dim: int,
    nbits: int,
    doc_maxlen: int,
    interpret=None,
):
    """Stage 4 over the compacted candidate-slice arrays + final top-k.

    Wraps the slices in a pool-local :class:`PlaidIndex` (IVF fields are
    1-element placeholders — stage 4 never reads them) so
    ``exact_stage4_impl`` runs unchanged, fused tail included: its CSR
    window gather works over ANY token array.
    """
    global _N_TRACES_B
    _N_TRACES_B += 1
    z = jnp.zeros((1,), jnp.int32)
    compact = PlaidIndex(
        centroids=centroids,
        centroids_q=centroids_q,
        centroids_scale=centroids_scale,
        codes=codes_c,
        residuals=res_c,
        tok_pid=z,
        doc_offsets=offs_c,
        doc_lens=lens_c,
        ivf_pids=z,
        ivf_offsets=z,
        ivf_lens=z,
        eivf_eids=z,
        eivf_offsets=z,
        eivf_lens=z,
        cutoffs=cutoffs,
        weights=weights,
        dim=dim,
        nbits=nbits,
        doc_maxlen=doc_maxlen,
        ivf_list_cap=1,
        eivf_list_cap=1,
    )
    exact = pl.exact_stage4_impl(
        compact, qs, q_masks, pos_pids, codes4, tok_valid4,
        params=params, interpret=interpret,
    )
    return pl.finalize_topk(exact, final_pids, params.k)


_phase_b_jit = jax.jit(
    _phase_b_impl,
    static_argnames=("params", "dim", "nbits", "doc_maxlen", "interpret"),
)


# --------------------------------------------------------------------------
# Host-side slice gather + reusable staging buffers
# --------------------------------------------------------------------------
class _StagingRing:
    """Two reusable host staging slots, round-robin per batch.

    ``jax.device_put`` sources the transfer from these buffers; reusing a
    stable allocation keeps the pages warm (pinned, on backends that pin
    host transfer sources), and TWO slots mean batch N+1's numpy fill never
    scribbles over the buffer batch N's async copy is still reading —
    that is what lets the serving tier overlap the H2D copy with the next
    admitted batch's phase A.
    """

    def __init__(self):
        self._slots = [{}, {}]
        self._turn = 0

    def _buf(self, slot: dict, key: str, shape, dtype) -> np.ndarray:
        buf = slot.get(key)
        need = int(np.prod(shape))
        if buf is None or buf.dtype != np.dtype(dtype) or buf.size < need:
            buf = np.zeros(max(need, 1), dtype)
            slot[key] = buf
        return buf[:need].reshape(shape)

    def take(self, t_cap: int, p_cap: int, pd: int):
        """Next slot's (codes, residuals, offsets, lens) staging views."""
        slot = self._slots[self._turn]
        self._turn = 1 - self._turn
        return (
            self._buf(slot, "codes", (t_cap,), np.int32),
            self._buf(slot, "res", (t_cap, pd), np.uint8),
            self._buf(slot, "offs", (p_cap + 1,), np.int32),
            self._buf(slot, "lens", (p_cap,), np.int32),
        )


@dataclasses.dataclass
class TransferStats:
    """Per-batch host->device accounting for the candidate-slice pull."""

    pool_docs: int  # distinct finalist passages across the batch
    slice_tokens: int  # exact CSR token count of those passages
    slice_bytes: int  # exact candidate-slice bytes (tokens * (pd+4))
    staged_bytes: int  # bytes actually device_put (pow2-padded staging)

    def as_dict(self) -> dict:
        return dataclasses.asdict(self)


# --------------------------------------------------------------------------
# The tiered engine
# --------------------------------------------------------------------------
class TieredEngine:
    """Batch search over a :class:`TieredIndex` via the two-phase pipeline.

    Drop-in for ``PlaidEngine.search_batch`` semantics (same clamp rule,
    same traced ``t_cs``, same optional ``funnel`` aux) with bitwise
    identical results; additionally keeps :class:`TransferStats` for the
    last batch (``last_transfer``) and running ``transfer_totals`` that the
    serving tier and benchmarks surface.
    """

    def __init__(
        self,
        tiered: TieredIndex,
        params: plaid.SearchParams | None = None,
        *,
        device_budget_bytes: int | None = None,
        interpret: bool | None = None,
    ):
        self.tiered = tiered
        self.params = params or plaid.SearchParams()
        self.interpret = interpret
        if device_budget_bytes is not None:
            got = tiered.device_nbytes()
            if got > device_budget_bytes:
                raise TieredBudgetError(
                    f"device tier needs {got} bytes but the budget is "
                    f"{device_budget_bytes}; shrink the corpus per partition "
                    "(exec.tiered.partition_tiered) or raise the budget"
                )
        self.device_budget_bytes = device_budget_bytes
        self._staging = _StagingRing()
        self.last_transfer: TransferStats | None = None
        self.transfer_totals = dict(
            batches=0, pool_docs=0, slice_tokens=0, slice_bytes=0,
            staged_bytes=0,
        )

    # -- pipeline params (the shared corpus clamp rule) --------------------
    def _pipeline_params(self) -> plaid.SearchParams:
        p = plaid.clamp_params(self.params, self.tiered.num_passages)
        return dataclasses.replace(p, t_cs=0.0)  # traced, not a cache key

    # -- host slice gather -------------------------------------------------
    def _gather_slices(self, final_pids: np.ndarray):
        """Dedup finalists, copy their CSR slices into staging buffers.

        Returns ``(pos_pids, codes_c, res_c, offs_c, lens_c, stats)`` where
        the compacted arrays are numpy staging views sized to pow2 buckets
        (stable phase-B shapes) and ``pos_pids`` maps each finalist lane to
        its pool-local row (-1 for padding lanes).
        """
        # lazy: repro.exec imports this module (exec.tiered), so the
        # package-level import would cycle
        from repro.exec.segments import pow2_bucket

        t = self.tiered
        pd = t.host_residuals.shape[1]
        L = t.device.doc_maxlen
        pool = np.unique(final_pids[final_pids >= 0]).astype(np.int64)
        lens = t.host_doc_lens[pool].astype(np.int64)
        starts = t.host_doc_offsets[pool].astype(np.int64)
        cum = np.zeros(pool.size + 1, np.int64)
        np.cumsum(lens, out=cum[1:])
        total = int(cum[-1])

        p_cap = pow2_bucket(max(pool.size, 1), lo=1)
        t_cap = pow2_bucket(max(total, 1), lo=L)
        codes_c, res_c, offs_c, lens_c = self._staging.take(t_cap, p_cap, pd)

        # one fancy-gather per payload reads exactly the slices' mmap pages
        tok_idx = np.repeat(starts - cum[:-1], lens) + np.arange(total)
        codes_c[:total] = t.host_codes[tok_idx]
        codes_c[total:] = 0
        res_c[:total] = t.host_residuals[tok_idx]
        res_c[total:] = 0
        offs_c[: pool.size + 1] = cum
        offs_c[pool.size + 1:] = total
        lens_c[: pool.size] = lens
        lens_c[pool.size:] = 0

        pos = np.searchsorted(pool, np.where(final_pids >= 0, final_pids, 0))
        pos_pids = np.where(final_pids >= 0, pos, -1).astype(np.int32)

        stats = TransferStats(
            pool_docs=int(pool.size),
            slice_tokens=total,
            slice_bytes=total * (pd + 4),
            staged_bytes=int(
                codes_c.nbytes + res_c.nbytes + offs_c.nbytes + lens_c.nbytes
                + pos_pids.nbytes
            ),
        )
        return pos_pids, codes_c, res_c, offs_c, lens_c, stats

    def _record(self, stats: TransferStats) -> None:
        self.last_transfer = stats
        tot = self.transfer_totals
        tot["batches"] += 1
        tot["pool_docs"] += stats.pool_docs
        tot["slice_tokens"] += stats.slice_tokens
        tot["slice_bytes"] += stats.slice_bytes
        tot["staged_bytes"] += stats.staged_bytes

    # -- search ------------------------------------------------------------
    def search_batch(
        self,
        qs,
        q_masks=None,
        t_cs=None,
        *,
        funnel: bool = False,
        alive=None,
    ):
        """(B, nq, d) queries -> ((B, k) scores, (B, k) pids[, FunnelStats]).

        Phase A runs on device against the stripped index; only the
        finalists' pids sync to host, only their CSR slices come back.
        """
        qs = jnp.asarray(qs)
        if q_masks is None:
            q_masks = jnp.ones(qs.shape[:2], jnp.float32)
        p = self._pipeline_params()
        t = jnp.asarray(
            self.params.t_cs if t_cs is None else t_cs, jnp.float32
        )
        dev = self.tiered.device
        final_pids, codes4, tok_valid4, extras = _phase_a_jit(
            dev, qs, q_masks, t,
            params=p, funnel=funnel, keep_blocks=not p.fused,
            interpret=self.interpret, alive=alive,
        )
        fp = np.asarray(final_pids)  # the one device->host sync point
        pos_pids, codes_c, res_c, offs_c, lens_c, stats = (
            self._gather_slices(fp)
        )
        self._record(stats)
        from repro.obs.trace import get_tracer

        with get_tracer().span(
            "tiered.transfer",
            slice_bytes=stats.slice_bytes,
            staged_bytes=stats.staged_bytes,
            pool_docs=stats.pool_docs,
        ):
            # async under the hood: the staging slot stays untouched until
            # the ring wraps, so the copy overlaps the caller's next phase A
            codes_d, res_d, offs_d, lens_d, pos_d = jax.device_put(
                (codes_c, res_c, offs_c, lens_c, pos_pids)
            )
        scores, pids = _phase_b_jit(
            qs, q_masks, final_pids, pos_d, codes4, tok_valid4,
            codes_d, res_d, offs_d, lens_d,
            dev.centroids, dev.centroids_q, dev.centroids_scale,
            dev.cutoffs, dev.weights,
            params=p, dim=dev.dim, nbits=dev.nbits,
            doc_maxlen=dev.doc_maxlen, interpret=self.interpret,
        )
        if funnel:
            return scores, pids, extras[-1]
        return scores, pids

    def search(self, q, q_mask=None, t_cs=None):
        """Single-query convenience: squeeze of a B=1 ``search_batch``."""
        qm = None if q_mask is None else jnp.asarray(q_mask)[None]
        scores, pids = self.search_batch(
            jnp.asarray(q)[None], qm, t_cs
        )
        return scores[0], pids[0]


# --------------------------------------------------------------------------
# Persistence: v2 tiered manifests (payloads as mmap-able .npy files)
# --------------------------------------------------------------------------
def save_tiered(path: str, index) -> None:
    """Write a tiered index directory: v2 manifest, ``storage: "tiered"``
    stamp, token payloads as raw ``.npy`` files next to ``arrays.npz`` so
    load can ``np.load(..., mmap_mode="r")`` them with no densification.

    Accepts a resident :class:`PlaidIndex` or a :class:`TieredIndex` (the
    O(Nt) side arrays a resident save would carry — ``tok_pid``,
    ``eivf_eids`` — are reconstructed host-side; they are derived data).
    """
    from repro.live import manifest as mf

    if isinstance(index, TieredIndex):
        t = index
        tok_pid = np.repeat(
            np.arange(t.num_passages, dtype=np.int32), t.host_doc_lens
        )
        full = dataclasses.replace(
            t.device,
            codes=t.host_codes,
            residuals=t.host_residuals,
            tok_pid=tok_pid,
            eivf_eids=np.argsort(t.host_codes, kind="stable").astype(
                np.int32
            ),
        )
    else:
        full = index
    mf.save_segmented(
        path, [full], [0], tombstones=None, generation=0, storage="tiered"
    )


def load_tiered(path: str) -> TieredIndex:
    """Open a tiered index directory: device tier uploaded, payloads mmap'd.

    The payload files are opened with ``mmap_mode="r"`` straight off the
    manifest — no load-time densification; pages fault in as slices are
    gathered.  ``codes`` are ALSO uploaded to the device tier (stages 2-3
    consume them there).  Raises the ``live.manifest`` typed errors on
    missing/corrupt payloads and rejects non-tiered layouts.
    """
    from repro.live import manifest as mf

    man = mf.read_manifest(path)
    if man.get("storage") != "tiered":
        raise ValueError(
            f"{path}: not a tiered index (storage="
            f"{man.get('storage', 'resident')!r}); use the resident loaders"
        )
    segs = man["segments"]
    if len(segs) != 1 or man.get("tombstones"):
        raise ValueError(
            f"{path}: tiered load supports exactly one live segment, found "
            f"{len(segs)} (tombstones={man.get('tombstones')!r}); compact "
            "before demoting to tiered storage"
        )
    arrays, static, payloads = mf.read_tiered_segment(
        os.path.join(path, segs[0]["name"]), segs[0]
    )
    dev = PlaidIndex(
        **{k: jnp.asarray(v) for k, v in arrays.items()},
        codes=jnp.asarray(payloads["codes"]),
        residuals=jnp.zeros((1, payloads["residuals"].shape[1]), jnp.uint8),
        tok_pid=jnp.zeros((1,), jnp.int32),
        eivf_eids=jnp.zeros((1,), jnp.int32),
        **static,
    )
    return TieredIndex(
        device=dev,
        host_codes=payloads["codes"],
        host_residuals=payloads["residuals"],
        host_doc_offsets=np.asarray(arrays["doc_offsets"], np.int32),
        host_doc_lens=np.asarray(arrays["doc_lens"], np.int32),
    )
