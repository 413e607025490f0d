"""Mesh-environment layer: the one home of mesh/sharding introspection.

The model stack uses JAX's mesh-context API (``jax.set_mesh`` /
``jax.sharding.get_abstract_mesh`` / ``AxisType``).  Every query returns
``None`` / no-ops when no mesh is active, which is the common
single-device path.

Public surface (the only sanctioned mesh introspection in this repo):

* ``make_mesh(shape, axis_names)``        — mesh with Auto axis types
* ``current_mesh()``                      — active mesh or ``None``
* ``axis_names()`` / ``axis_sizes()``     — ambient-mesh introspection
* ``mesh_size(mesh, axes)``               — product of named axis extents
* ``mesh_context(mesh)``                  — enter ``mesh`` as the ambient mesh
* ``with_sharding_constraint(x, spec)``   — ambient-mesh constraint
* ``shard_map(f, mesh=..., ...)``         — ``jax.shard_map``
"""

from __future__ import annotations

import contextlib
from typing import Dict, Tuple, Union

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

Axes = Union[None, str, Tuple[str, ...]]


def make_mesh(axis_shapes, axis_names, *, devices=None) -> Mesh:
    """``jax.make_mesh`` with explicit Auto axis types (the future default
    is ``Explicit``, which the model stack's constraints do not use)."""
    auto = (jax.sharding.AxisType.Auto,) * len(axis_names)
    return jax.make_mesh(axis_shapes, axis_names, axis_types=auto,
                         devices=devices)


def current_mesh():
    """The active (abstract) mesh, or ``None`` when no mesh context is in
    effect."""
    m = jax.sharding.get_abstract_mesh()
    if m is None or getattr(m, "empty", False) or not m.axis_names:
        return None
    return m


def axis_names() -> Tuple[str, ...]:
    """Axis names of the active mesh (``()`` when unmeshed)."""
    m = current_mesh()
    return () if m is None else tuple(m.axis_names)


def axis_sizes(mesh=None) -> Dict[str, int]:
    """``{axis_name: extent}`` for ``mesh`` (default: the active mesh)."""
    m = current_mesh() if mesh is None else mesh
    if m is None:
        return {}
    return dict(m.shape)


def mesh_size(mesh, axes: Axes) -> int:
    """Product of the named axis extents (1 for ``None`` / absent mesh)."""
    if mesh is None or axes is None:
        return 1
    if isinstance(axes, str):
        axes = (axes,)
    sizes = axis_sizes(mesh)
    n = 1
    for a in axes:
        n *= sizes[a]
    return n


@contextlib.contextmanager
def mesh_context(mesh: Mesh):
    """Enter ``mesh`` as the ambient mesh (``jax.set_mesh``)."""
    with jax.set_mesh(mesh):
        yield mesh


def with_sharding_constraint(x: jax.Array, spec: P) -> jax.Array:
    """Constrain ``x`` to ``spec`` under the ambient mesh (no-op unmeshed).
    A concrete mesh is bound into a NamedSharding; the abstract mesh of a
    ``mesh_context`` takes the bare PartitionSpec."""
    m = current_mesh()
    if m is None:
        return x
    if isinstance(m, Mesh):
        return jax.lax.with_sharding_constraint(x, NamedSharding(m, spec))
    return jax.lax.with_sharding_constraint(x, spec)


def shard_map(f, *, mesh, in_specs, out_specs, check_rep: bool = False):
    """``jax.shard_map``; ``check_rep`` maps to its ``check_vma``."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check_rep)
