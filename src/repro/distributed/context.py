"""Trace-time mesh context: lets model-internal code (e.g. MoE dispatch)
apply ``with_sharding_constraint`` without threading the mesh through every
signature.  Set by the launch/dry-run layer around ``.lower()`` / execution;
a no-op when unset (single-device tests)."""

from __future__ import annotations

import contextlib
from typing import Optional

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

_MESH: Optional[Mesh] = None


def mesh_ctx() -> Optional[Mesh]:
    return _MESH


@contextlib.contextmanager
def use_mesh_ctx(mesh: Optional[Mesh]):
    global _MESH
    old = _MESH
    _MESH = mesh
    try:
        yield
    finally:
        _MESH = old


def constrain(x: jax.Array, *axes) -> jax.Array:
    """Best-effort sharding constraint: ``axes`` are mesh-axis names (or
    tuples of names, or None) per dimension.  Dims that don't divide are
    left unconstrained; no-op without a mesh context."""
    mesh = _MESH
    if mesh is None:
        return x
    parts = []
    for dim, ax in zip(x.shape, axes):
        if ax is None:
            parts.append(None)
            continue
        names = ax if isinstance(ax, tuple) else (ax,)
        names = tuple(a for a in names if a in mesh.axis_names)
        size = 1
        for a in names:
            size *= mesh.shape[a]
        if not names or size <= 0 or dim % size != 0:
            parts.append(None)
        else:
            parts.append(names if len(names) > 1 else names[0])
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, P(*parts)))


def dp() -> tuple:
    """The data-parallel axes present in the current mesh context."""
    mesh = _MESH
    if mesh is None:
        return ()
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def shard_map(f, *, mesh, in_specs, out_specs):
    """``jax.shard_map`` with replication checking off: callers here do
    manual collectives the checker can't type."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)
