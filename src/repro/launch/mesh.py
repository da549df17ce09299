"""Mesh factories for the production deployment + multi-host bring-up.

Single pod = 16x16 = 256 chips (TPU v5e pod slice); multi-pod adds a leading
"pod" axis (2 pods = 512 chips).  FUNCTIONS, not module constants — merely
importing this module never touches jax device state (the dry-run must set
XLA_FLAGS before the first jax call).

Multi-host: :func:`init_distributed` wraps ``jax.distributed.initialize``
(idempotent, env-var aware).  After it returns, ``jax.devices()`` lists the
GLOBAL device set across every participating process, so the existing
``exec.sharded`` plans — built on ``shard_map`` over a mesh +
``merge_topk`` over the mesh axis — span real hosts with no further code:
:func:`make_multihost_mesh` just shapes those global devices as
``("host", "model")``.
"""
from __future__ import annotations

import os

import jax

from repro.compat import make_mesh


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_local_mesh():
    """1-device mesh with the standard axis names (tests / smoke runs)."""
    return make_mesh((1, 1), ("data", "model"))


#: set by init_distributed so repeat calls (several retriever loads in one
#: process) stay no-ops — jax.distributed.initialize raises on re-init.
_DISTRIBUTED_INITIALIZED = False


def init_distributed(
    coordinator_address: str | None = None,
    num_processes: int | None = None,
    process_id: int | None = None,
    *,
    local_device_ids=None,
) -> bool:
    """Join (or bootstrap) a multi-host jax runtime; returns True if this
    call performed the initialization, False if it was already done.

    Arguments default to the standard env vars (``JAX_COORDINATOR_ADDRESS``
    / ``JAX_NUM_PROCESSES`` / ``JAX_PROCESS_ID``) so launchers can configure
    processes without threading arguments through the stack; with neither
    arguments nor env vars present this is a single-process no-op — the
    same binary runs laptop-local and pod-wide.

    Must run before the first jax device query in the process (jax backends
    initialize lazily and lock in the local-only device set).
    """
    global _DISTRIBUTED_INITIALIZED
    if _DISTRIBUTED_INITIALIZED:
        return False
    coordinator_address = coordinator_address or os.environ.get(
        "JAX_COORDINATOR_ADDRESS"
    )
    if num_processes is None and "JAX_NUM_PROCESSES" in os.environ:
        num_processes = int(os.environ["JAX_NUM_PROCESSES"])
    if process_id is None and "JAX_PROCESS_ID" in os.environ:
        process_id = int(os.environ["JAX_PROCESS_ID"])
    if coordinator_address is None or num_processes is None:
        return False  # single-process run: nothing to join
    jax.distributed.initialize(
        coordinator_address=coordinator_address,
        num_processes=num_processes,
        process_id=process_id,
        local_device_ids=local_device_ids,
    )
    _DISTRIBUTED_INITIALIZED = True
    return True


def is_multihost() -> bool:
    """True when this process is one of several in a jax runtime."""
    return jax.process_count() > 1


def make_multihost_mesh(*, axis: str = "model"):
    """Mesh over the GLOBAL device set (all hosts), 1-D along ``axis``.

    Call :func:`init_distributed` first; afterwards ``jax.devices()``
    already enumerates every process' devices, so ``exec.sharded`` plans
    built on this mesh shard documents across hosts and merge through the
    same ``merge_topk(axis_name=...)`` they use locally — the cross-host
    all-gather is XLA's, not ours.
    """
    return make_mesh((len(jax.devices()),), (axis,))


def num_chips(mesh) -> int:
    n = 1
    for v in mesh.shape.values():
        n *= v
    return n
