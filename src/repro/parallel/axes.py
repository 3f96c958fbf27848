"""Logical-axis → mesh-axis rules and the sharding-constraint context.

Models never mention mesh axes.  They call ``lc(x, "batch", "seq", "embed")``
(logical constraint) on activations; parameters carry logical axes in their
:class:`~repro.models.common.ParamDef`.  The runtime activates a
:class:`MeshRules` per layer-group — derived from the group's
``LayerStrategy`` — and GSPMD does the rest.  Outside any context ``lc`` is a
no-op, so the same model code runs single-device in smoke tests.
"""
from __future__ import annotations

import contextlib
import threading
from dataclasses import dataclass, field
from typing import Optional, Sequence

import jax

from repro.compat import Mesh, NamedSharding, P, current_mesh_context

_CTX = threading.local()


MeshAssignment = tuple[str, ...]  # e.g. ("pod", "data") for the dp logical axis


@dataclass(frozen=True)
class MeshRules:
    """Mapping from logical axis names to mesh axis names (or None).

    ``ring`` names the mesh axis carrying context parallelism (ring
    flash-attention) for the active layer group, if any — attention reads it
    via :func:`ring_context` to route through parallel/context.py instead of
    a plain sharding constraint (a constraint alone cannot express the
    k/v ring rotation)."""

    rules: dict = field(default_factory=dict)
    mesh: Optional[Mesh] = None
    ring: Optional[str] = None

    def spec(self, logical_axes: Sequence[str | None]) -> P:
        used: set[str] = set()
        out = []
        for ax in logical_axes:
            target = self.rules.get(ax) if ax is not None else None
            if target is None:
                out.append(None)
                continue
            targets = target if isinstance(target, tuple) else (target,)
            # A mesh axis may appear at most once in a PartitionSpec; on
            # conflict the later logical axis stays unsharded.
            fresh = tuple(t for t in targets if t not in used)
            if not fresh:
                out.append(None)
                continue
            used.update(fresh)
            out.append(fresh if len(fresh) > 1 else fresh[0])
        while out and out[-1] is None:
            out.pop()
        return P(*out)

    def sharding(self, logical_axes: Sequence[str | None]) -> Optional[NamedSharding]:
        if self.mesh is None:
            return None
        return NamedSharding(self.mesh, self.spec(logical_axes))

    def axis_size(self, logical: str) -> int:
        """Total shard count the rules assign to a logical axis (1 if unsharded)."""
        target = self.rules.get(logical)
        if target is None or self.mesh is None:
            return 1
        targets = target if isinstance(target, tuple) else (target,)
        n = 1
        for t in targets:
            n *= self.mesh.shape[t]
        return n

    def spec_for_shape(self, logical_axes: Sequence[str | None],
                       shape: Sequence[int]) -> P:
        """Like ``spec`` but drops any mapping whose mesh-axis product does not
        divide the dim size — jit in/out shardings require divisibility."""
        used: set[str] = set()
        out = []
        for ax, dim in zip(logical_axes, shape):
            target = self.rules.get(ax) if ax is not None else None
            if target is None:
                out.append(None)
                continue
            targets = target if isinstance(target, tuple) else (target,)
            fresh = tuple(t for t in targets if t not in used)
            if not fresh:
                out.append(None)
                continue
            if self.mesh is not None:
                n = 1
                for t in fresh:
                    n *= self.mesh.shape[t]
                if n == 0 or dim % n != 0:
                    out.append(None)
                    continue
            used.update(fresh)
            out.append(fresh if len(fresh) > 1 else fresh[0])
        while out and out[-1] is None:
            out.pop()
        return P(*out)


@contextlib.contextmanager
def axis_rules(rules: Optional[MeshRules]):
    prev = getattr(_CTX, "rules", None)
    _CTX.rules = rules
    try:
        yield
    finally:
        _CTX.rules = prev


def current_rules() -> Optional[MeshRules]:
    return getattr(_CTX, "rules", None)


@dataclass(frozen=True)
class RingContext:
    """Active context-parallelism site: attention should run as a ring over
    ``mesh.shape[axis]`` sequence shards (see parallel/context.py)."""

    mesh: Mesh
    axis: str
    cp: int


def ring_context() -> Optional[RingContext]:
    """Ring-attention context from the active rules, or None.

    Returns None when no rules are active, the rules carry no ring axis, the
    axis is only 1 wide, or the axis is already Manual in the current
    shard_map region (the ring was applied by an enclosing transform)."""
    rules = current_rules()
    if rules is None or rules.mesh is None or not rules.ring:
        return None
    mesh = rules.mesh
    if rules.ring not in mesh.axis_names:
        return None
    _, manual = current_mesh_context(mesh)
    if rules.ring in manual:
        return None
    cp = int(mesh.shape[rules.ring])
    if cp <= 1:
        return None
    return RingContext(mesh=mesh, axis=rules.ring, cp=cp)


def lc(x, *logical_axes: str | None):
    """Logical sharding constraint on an activation (no-op outside a mesh).

    Inside a partial-auto ``shard_map`` region the constraint is built on the
    mesh :func:`repro.compat.current_mesh_context` reports — the current
    abstract mesh (a sharding built on the outer concrete mesh would be
    rejected there).  Rule targets that are manual in the current context
    are dropped (the manual axis is already fully applied by shard_map
    itself).
    """
    rules = current_rules()
    if rules is None or rules.mesh is None:
        return x
    mesh = rules.mesh
    ctx_mesh, manual = current_mesh_context(mesh)
    if manual:
        filtered = {}
        for k, v in rules.rules.items():
            targets = v if isinstance(v, tuple) else (v,)
            keep = tuple(t for t in targets if t not in manual)
            if keep:
                filtered[k] = keep if len(keep) > 1 else keep[0]
        rules = MeshRules(rules=filtered, mesh=mesh, ring=rules.ring)
    spec = rules.spec(logical_axes)
    if all(s is None for s in spec):
        return x
    return jax.lax.with_sharding_constraint(x, NamedSharding(ctx_mesh, spec))
