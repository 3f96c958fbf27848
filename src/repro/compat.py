"""Mesh / sharding / shard_map / jit entry points for the installed JAX (0.9).

The parallel runtime imports these names from here, never from
``jax.sharding``/``jax.experimental`` directly, so that one module owns the
decisions the runtime depends on:

* **axis types.** ``jax.make_mesh`` builds *Explicit* axes by default, and the
  runtime's sharding constraints (``jax.lax.with_sharding_constraint`` in
  ``parallel/axes.py``, ``runtime/train.py``, ``parallel/pipeline.py``)
  refer to *Auto* axes only.  :func:`make_mesh` therefore asks for Auto axes.
* **manual axes.** Inside a partial-auto ``shard_map`` body the abstract-mesh
  context marks the manual axes; :func:`current_mesh_context` is the single
  query point ``lc()`` uses to drop them from its rules.
"""
from __future__ import annotations

from typing import Any, Callable, Iterable, Optional

import jax
from jax.sharding import AbstractMesh, AxisType, Mesh, NamedSharding, PartitionSpec  # noqa: F401  (re-exported)

P = PartitionSpec

#: ``jax.jit``; the ``compat-jit`` lint rule routes every caller through here.
jit = jax.jit


# ---------------------------------------------------------------------------
# mesh construction
# ---------------------------------------------------------------------------

def make_mesh(shape: Iterable[int], axes: Iterable[str],
              devices: Optional[Iterable[Any]] = None) -> Mesh:
    """``jax.make_mesh`` with every axis of type Auto.

    ``devices`` restricts the mesh to an explicit device subset — the live
    elastic-resize path (runtime/resize.py) builds the shrunk mesh over the
    surviving devices while the departed ones idle.
    """
    shape, axes = tuple(shape), tuple(axes)
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes),
                         devices=None if devices is None else list(devices))


def abstract_mesh(shape: Iterable[int], axes: Iterable[str]) -> AbstractMesh:
    """Device-free mesh (Auto axes) for sharding-rule derivation."""
    return AbstractMesh(tuple(shape), tuple(axes))


def current_mesh_context(mesh: Mesh) -> tuple[Any, frozenset[str]]:
    """(mesh to build sharding constraints on, currently-Manual axis names).

    When an abstract mesh context matching ``mesh``'s axes is active (i.e. we
    are inside a shard_map region), constraints must be built on *it*, and its
    Manual-typed axes must be dropped from the rules.
    """
    ctx = jax.sharding.get_abstract_mesh()
    if ctx is not None and not ctx.empty and set(ctx.axis_names) == set(mesh.axis_names):
        manual = frozenset(n for n, t in zip(ctx.axis_names, ctx.axis_types)
                           if t == AxisType.Manual)
        return ctx, manual
    return mesh, frozenset()


# ---------------------------------------------------------------------------
# shard_map
# ---------------------------------------------------------------------------

def shard_map(
    f: Callable,
    *,
    mesh: Mesh,
    in_specs: Any,
    out_specs: Any,
    axis_names: Optional[Iterable[str]] = None,
    check_vma: bool = True,
) -> Callable:
    """``jax.shard_map``; ``axis_names`` is the set of mesh axes the body is
    *manual* over (remaining axes stay auto so GSPMD constraints keep working
    inside).  ``None`` means fully manual (every axis)."""
    manual = set(axis_names) if axis_names is not None else set(mesh.axis_names)
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
                         axis_names=manual, check_vma=check_vma)


# ---------------------------------------------------------------------------
# compiled-artifact analyses
# ---------------------------------------------------------------------------

def cost_analysis(computation) -> dict:
    """``.cost_analysis()`` of a Lowered/Compiled computation as a flat
    ``{metric: value}`` dict (empty when XLA reports nothing)."""
    ca = computation.cost_analysis()
    return dict(ca) if ca else {}
