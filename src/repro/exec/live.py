"""Execution plans for live (mutable) indexes — sharded or single-device.

``LiveExecutor`` turns a :class:`repro.live.LiveIndex` snapshot into an
:class:`repro.exec.plan.ExecutionPlan` and keeps every cache needed to make
repeat searches cheap:

* **partition structure** — the base segment is one partition group
  (device-sharded over a mesh via ``shard_index`` when a mesh is given,
  else the degenerate one-segment stacked program); all delta segments
  stack into a second group under ONE jit (``repro.exec.segments``).  The
  plan's final cross-group merge is the same ``merge_topk`` the groups use
  internally.
* **compiled programs** are cached per static bucket / shard layout, so a
  fixed segment-count bucket costs exactly one pipeline trace however many
  deltas it holds (asserted in ``tests/test_exec.py``).
* **packed arrays** are cached per segment list; the alive bitmap, pid
  offsets and ``t_cs`` are traced, so deletes and threshold sweeps rebuild
  only the (cheap) plan wiring and never recompile.

Mutations stay on the ``LiveIndex`` itself (the ``MutableRetriever``
surface): adds append delta segments (replicated — small by construction),
deletes flip the tombstone bitmap, and a compaction swaps in a new base,
which the executor notices by segment id and re-shards host-side.
"""
from __future__ import annotations

import dataclasses
import threading

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import plaid
from repro.exec import segments as seg_exec
from repro.exec import sharded as shard_exec
from repro.exec.plan import ExecutionPlan


def mesh_for_shards(n_shards: int):
    """A 1-axis ("data",) mesh over the first ``n_shards`` visible devices.

    ``jax.devices()`` is the GLOBAL device set: after
    ``launch.mesh.init_distributed`` it spans every participating host, so
    the same sharded plans scale from one host's (possibly XLA-faked)
    devices to a real multi-host deployment with no call-site change.
    """
    devices = jax.devices()
    if n_shards > len(devices):
        hint = (
            "join more hosts (launch.mesh.init_distributed)"
            if jax.process_count() > 1
            else "run under XLA_FLAGS=--xla_force_host_platform_"
            "device_count=N, join more hosts via "
            "launch.mesh.init_distributed,"
        )
        raise ValueError(
            f"n_shards={n_shards} exceeds the {len(devices)} visible "
            f"devices across {jax.process_count()} process(es); {hint} "
            "or lower n_shards"
        )
    return jax.sharding.Mesh(
        np.asarray(devices[:n_shards]).reshape(n_shards), ("data",)
    )


class LiveExecutor:
    """Plan builder/cache over one LiveIndex (see module docstring)."""

    def __init__(
        self,
        live,
        params: plaid.SearchParams | None = None,
        *,
        mesh=None,
        n_shards: int | None = None,
    ):
        self.live = live
        self.params = params or plaid.SearchParams()
        if mesh is None and n_shards is not None and n_shards > 1:
            mesh = mesh_for_shards(n_shards)
        self.mesh = mesh
        self.n_shards = (
            shard_exec.n_doc_shards(mesh) if mesh is not None else 1
        )
        if n_shards is not None and self.n_shards != max(n_shards, 1):
            raise ValueError(
                f"n_shards={n_shards} must equal the mesh's doc-shard "
                f"count ({self.n_shards}); build the mesh to match"
            )
        # guards every cache below: plan building mutates them, and one
        # retriever is routinely shared between a BatchingServer dispatcher
        # and direct callers.  Execution runs OUTSIDE the lock — plans are
        # immutable closures over immutable arrays.
        self._lock = threading.Lock()
        self._stacked_fns: dict = {}  # (bucket, interpret, funnel) -> run
        self._packed: dict = {}  # (seg_ids, bucket) -> (stacked, shared)
        self._base_shards = None  # dict(sid, idx, meta, per, fns)
        self._plan_key = None
        self._plan = None

    # ---- partition groups -------------------------------------------------
    def _stacked_group(
        self, segments, seg_ids, offsets, alive, interpret, funnel
    ):
        bucket = seg_exec.bucket_for(segments)
        pkey = (tuple(seg_ids), bucket)
        if pkey not in self._packed:
            self._packed[pkey] = seg_exec.pack_segments(segments, bucket)
        stacked, shared = self._packed[pkey]
        fkey = (bucket, interpret, funnel)
        if fkey not in self._stacked_fns:
            self._stacked_fns[fkey] = seg_exec.make_stacked_search(
                self.params, bucket, interpret=interpret, funnel=funnel
            )
        fn = self._stacked_fns[fkey]
        offs = seg_exec.pack_offsets(offsets, bucket)
        alive_rows = seg_exec.pack_alive(alive, bucket)

        def group(qs, q_masks, t_cs):
            return fn(stacked, shared, qs, q_masks, t_cs, offs, alive_rows)

        return group, pkey

    def _sharded_base_group(self, base, base_sid, alive, interpret, funnel):
        from repro.core.engine_sharded import shard_index

        st = self._base_shards
        if st is None or st["sid"] != base_sid:
            idx_dict, meta, per = shard_index(
                base, self.n_shards, mesh=self.mesh
            )
            st = dict(sid=base_sid, idx=idx_dict, meta=meta, per=per, fns={})
            self._base_shards = st
        fn_key = (interpret, funnel)
        if fn_key not in st["fns"]:
            p = dataclasses.replace(
                self.params,
                # stage-1 bound is per shard: clamp to the shard's corpus
                candidate_cap=min(
                    self.params.candidate_cap, max(st["per"], 2)
                ),
            )
            st["fns"][fn_key] = shard_exec.make_sharded_search(
                self.mesh,
                p,
                docs_per_shard=st["per"],
                static_meta=st["meta"],
                interpret=interpret,
                funnel=funnel,
            )
        fn = st["fns"][fn_key]
        # base tombstones in the padded sharded pid space (pads are dead)
        padded = np.zeros(self.n_shards * st["per"], bool)
        mask = np.asarray(alive, bool)
        padded[: mask.shape[0]] = mask
        alive_arr = jnp.asarray(padded)
        idx = st["idx"]

        def group(qs, q_masks, t_cs):
            return fn(idx, qs, q_masks, t_cs, alive_arr)

        return group

    # ---- plan assembly ----------------------------------------------------
    def plan_for(
        self, snapshot, interpret: bool | None = None, funnel: bool = False
    ):
        """The (cached) ExecutionPlan for one LiveIndex snapshot."""
        key = (snapshot.generation, interpret, funnel)
        with self._lock:
            if self._plan_key == key:
                return self._plan
            return self._build_plan(snapshot, interpret, funnel, key)

    def _build_plan(self, snapshot, interpret, funnel, key):
        groups, live_pkeys = [], set()
        segs, sids = snapshot.segments, snapshot.seg_ids
        if self.mesh is not None:
            groups.append(
                self._sharded_base_group(
                    segs[0], sids[0], snapshot.alive[0], interpret, funnel
                )
            )
        else:
            g, pkey = self._stacked_group(
                segs[:1], sids[:1], snapshot.offsets[:1],
                snapshot.alive[:1], interpret, funnel,
            )
            groups.append(g)
            live_pkeys.add(pkey)
        if len(segs) > 1:
            g, pkey = self._stacked_group(
                segs[1:], sids[1:], snapshot.offsets[1:],
                snapshot.alive[1:], interpret, funnel,
            )
            groups.append(g)
            live_pkeys.add(pkey)
        # drop packed arrays no current segment list references (post-
        # compaction the old delta stack would otherwise pin device memory)
        self._packed = {
            k: v for k, v in self._packed.items() if k in live_pkeys
        }
        plan = ExecutionPlan(tuple(groups), self.params.k, funnel=funnel)
        self._plan_key, self._plan = key, plan
        return plan

    # ---- search -----------------------------------------------------------
    def search_batch(
        self, qs, q_masks=None, *, t_cs=None,
        interpret: bool | None = None, funnel: bool = False,
    ):
        """qs: (B, nq, dim) -> ((B, k) scores, (B, k) global pids[,
        merged obs.FunnelStats when ``funnel=True``])."""
        if q_masks is None:
            q_masks = jnp.ones(qs.shape[:2], jnp.float32)
        t = self.params.t_cs if t_cs is None else t_cs
        snapshot = self.live.snapshot()
        plan = self.plan_for(snapshot, interpret, funnel)
        return plan.search_batch(qs, q_masks, t)

    def search(self, q, q_mask=None, *, t_cs=None, interpret=None,
               funnel: bool = False):
        """q: (nq, dim) -> ((k,), (k,)).  B=1 squeeze of the batch path."""
        mask = None if q_mask is None else q_mask[None]
        out = self.search_batch(
            q[None], mask, t_cs=t_cs, interpret=interpret, funnel=funnel
        )
        scores, pids, *aux = out
        if funnel:
            fs = aux[0]
            return scores[0], pids[0], type(fs)(*(v[0] for v in fs))
        return scores[0], pids[0]
