"""The repo's one home for jax API drift (installed floor: jax 0.9).

* ``shard_map`` — keyword-only, with the repo's ``check_rep=`` spelling of
  jax's ``check_vma`` knob.
* ``make_mesh`` — ``jax.make_mesh`` makes ``Explicit`` axes by default, which
  turns every sharded operand's layout into part of its type (a
  ``dynamic_update_slice`` inside a sharded jit then rejects mismatched
  operand/update shardings).  The repo's meshes are ``Auto``: sharding is
  placed by ``shard_map`` specs and ``NamedSharding`` puts, and XLA
  propagates the rest.

Everything here is import-time cheap and side-effect free.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def shard_map(f, *, mesh, in_specs, out_specs, check_rep=False):
    return jax.shard_map(
        f, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
        check_vma=check_rep,
    )


def make_mesh(axis_shapes, axis_names, *, devices=None):
    """``jax.make_mesh`` with ``Auto`` axes (see module docstring)."""
    return jax.make_mesh(
        tuple(axis_shapes),
        tuple(axis_names),
        axis_types=(AxisType.Auto,) * len(axis_names),
        devices=devices,
    )
