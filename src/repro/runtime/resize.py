"""Live elastic resize: in-memory state migration onto a replanned mesh.

The elastic flow before this module was *plan-only*: ``runtime/elastic.py``
re-searched the (pp × cp × schedule × strategy) space for the surviving
device count, but realizing the new plan meant writing a checkpoint and
restarting the process.  This module closes the loop in memory:

1. **Canonicalize** — the old trainer's ``ungroup`` hook folds its layout
   (scan groups for the GSPMD trainer, pipeline stages for
   ``PipelineTrainer``) back into the canonical stacked-block pytree the
   checkpoint format also uses.  Optimizer ``m``/``v`` mirror the parameter
   tree, so the same hook canonicalizes them.
2. **Re-layout** — the new trainer's ``place_params`` / ``place_opt_state``
   hooks regroup/restage for the new plan and ``jax.device_put`` every leaf
   onto the new mesh's ``NamedSharding``s.  dp/tp/cp axis changes are pure
   resharding; pp changes go through the stage/unstage hooks; a departed
   device simply stops appearing in any sharding.
3. **Carry** — :class:`CarryState` moves the step counter, host RNG key and
   data cursor across the swap, so training resumes at the next step.

Because step 1/2 never serialize (raw device buffers in, raw device buffers
out) the migrated state is **bitwise identical** to what the
checkpoint-restore path produces — :func:`migrate_via_checkpoint` keeps that
path alive as the fallback for real membership loss (where the old mesh's
buffers are gone) and as the equivalence oracle the tests and the
``benchmarks/elastic_resize.py`` suite assert against.
"""
from __future__ import annotations

import dataclasses
import pathlib
import tempfile
import time
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.core.strategy import ExecutionPlan
from repro.runtime import checkpoint as ckpt_lib
from repro.runtime.train import construct_hybrid_parallel_model
from repro.runtime.train_pp import PipelineTrainer


# --------------------------------------------------------------------------
# plan diff
# --------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class MigrationSpec:
    """Diff between two :class:`ExecutionPlan`s: which mesh axes resize,
    which parallelism degrees change, and whether the parameter layout
    (scan groups / pipeline stages) must be rebuilt rather than resharded."""

    old_mesh: tuple[tuple[int, ...], tuple[str, ...]]
    new_mesh: tuple[tuple[int, ...], tuple[str, ...]]
    axis_resize: dict[str, tuple[int, int]]   # axis -> (old, new), changed only
    tp: tuple[int, int]
    cp: tuple[int, int]
    pp: tuple[int, int]
    schedule: tuple[str, str]
    grad_accum: tuple[int, int]
    restage: bool      # pipeline stage layout differs (stage/unstage needed)
    regroup: bool      # scan-group boundaries or strategies differ

    @property
    def mesh_changed(self) -> bool:
        return self.old_mesh != self.new_mesh

    @property
    def devices(self) -> tuple[int, int]:
        old = 1
        for s in self.old_mesh[0]:
            old *= s
        new = 1
        for s in self.new_mesh[0]:
            new *= s
        return old, new

    def summary(self) -> str:
        o, n = self.devices
        bits = [f"{o}->{n} devices"]
        for axis, (a, b) in sorted(self.axis_resize.items()):
            bits.append(f"{axis} {a}->{b}")
        if self.tp[0] != self.tp[1]:
            bits.append(f"tp {self.tp[0]}->{self.tp[1]}")
        if self.cp[0] != self.cp[1]:
            bits.append(f"cp {self.cp[0]}->{self.cp[1]}")
        if self.restage:
            bits.append(f"pp {self.pp[0]}/{self.schedule[0]}"
                        f"->{self.pp[1]}/{self.schedule[1]} (restage)")
        if self.regroup:
            bits.append("regroup")
        if self.grad_accum[0] != self.grad_accum[1]:
            bits.append(f"ga {self.grad_accum[0]}->{self.grad_accum[1]}")
        return ", ".join(bits)


def _group_key(plan: ExecutionPlan) -> tuple:
    return tuple((g.start, g.stop, g.strategy) for g in plan.groups())


def diff_plans(old: ExecutionPlan, new: ExecutionPlan) -> MigrationSpec:
    """Pure plan diff — no device state; drives logging and lets callers
    pick the cheap path (e.g. nothing to do when only grad_accum moved)."""
    sizes_old = dict(zip(old.mesh_axes, old.mesh_shape))
    sizes_new = dict(zip(new.mesh_axes, new.mesh_shape))
    axis_resize = {
        a: (sizes_old.get(a, 1), sizes_new.get(a, 1))
        for a in sorted(set(sizes_old) | set(sizes_new))
        if sizes_old.get(a, 1) != sizes_new.get(a, 1)
    }
    restage = (old.pp != new.pp
               or (new.pp > 1 and old.pp_interleave != new.pp_interleave))
    return MigrationSpec(
        old_mesh=(tuple(old.mesh_shape), tuple(old.mesh_axes)),
        new_mesh=(tuple(new.mesh_shape), tuple(new.mesh_axes)),
        axis_resize=axis_resize,
        tp=(old.default_strategy.tp, new.default_strategy.tp),
        cp=(old.default_strategy.cp, new.default_strategy.cp),
        pp=(old.pp, new.pp),
        schedule=(old.pp_schedule, new.pp_schedule),
        grad_accum=(old.grad_accum, new.grad_accum),
        restage=restage,
        regroup=_group_key(old) != _group_key(new),
    )


# --------------------------------------------------------------------------
# carried (non-array) training state
# --------------------------------------------------------------------------

@dataclasses.dataclass
class CarryState:
    """Training state that rides along besides params/opt-state: the loop
    step, the data cursor (global samples drawn — SyntheticDataset is keyed
    by sample id, so this is the only iterator state), and the host RNG key.
    All host-side, so carrying it over a mesh swap is a copy, never a
    collective."""

    step: int
    samples_seen: int = 0
    rng: Optional[Any] = None

    def carried(self) -> "CarryState":
        rng = None if self.rng is None else jnp.asarray(jax.device_get(self.rng))
        return CarryState(step=self.step, samples_seen=self.samples_seen, rng=rng)


@dataclasses.dataclass
class MigrationReport:
    spec: MigrationSpec
    seconds: float
    bytes_moved: int
    path: str                           # "in-memory" | "checkpoint"

    def summary(self) -> str:
        return (f"{self.path} migration: {self.spec.summary()} | "
                f"{self.bytes_moved / 1e6:.1f} MB in {self.seconds * 1e3:.1f} ms")


# --------------------------------------------------------------------------
# trainers
# --------------------------------------------------------------------------

def make_trainer(model, plan: ExecutionPlan, mesh, opt_cfg=None):
    """The runtime that realizes ``plan``: PipelineTrainer when the plan
    stages the block stack, the GSPMD hybrid trainer otherwise."""
    if plan.pp > 1:
        kw = {"opt_cfg": opt_cfg} if opt_cfg is not None else {}
        return PipelineTrainer(model, plan, mesh, **kw)
    return construct_hybrid_parallel_model(model, plan, mesh, opt_cfg=opt_cfg)


def canonical_state(trainer, params, opt_state):
    """Fold a trainer's layout back into the canonical (ungrouped, unstaged)
    pytrees — the same form checkpoints store.  (No host snapshot: migration
    reshards on device; the trainers' ``checkpoint_state`` hooks are the
    snapshot-starting variant for the async writer.)"""
    return ckpt_lib.canonical_checkpoint_state(trainer, params, opt_state,
                                               snapshot=False)


def _tree_bytes(*trees) -> int:
    total = 0
    for tree in trees:
        if tree is None:
            continue
        for leaf in jax.tree.leaves(tree):
            if hasattr(leaf, "size") and hasattr(leaf, "dtype"):
                total += int(leaf.size) * jnp.dtype(leaf.dtype).itemsize
    return total


def _block(*trees):
    for tree in trees:
        if tree is not None:
            jax.block_until_ready(tree)


# --------------------------------------------------------------------------
# migration paths
# --------------------------------------------------------------------------

def migrate(old_trainer, new_trainer, params, opt_state=None,
            carry: Optional[CarryState] = None):
    """In-memory migration: old layout -> canonical -> new layout, entirely
    via ``device_put`` resharding (no host serialization).  Returns
    ``(params, opt_state, carry, report)`` laid out for ``new_trainer``."""
    t0 = time.perf_counter()
    spec = diff_plans(old_trainer.plan, new_trainer.plan)
    canon_p, canon_o = canonical_state(old_trainer, params, opt_state)
    new_p = new_trainer.place_params(canon_p)
    new_o = None if canon_o is None else new_trainer.place_opt_state(canon_o)
    _block(new_p, new_o)
    new_carry = carry.carried() if carry is not None else None
    report = MigrationReport(spec=spec, seconds=time.perf_counter() - t0,
                             bytes_moved=_tree_bytes(new_p, new_o),
                             path="in-memory")
    return new_p, new_o, new_carry, report


def migrate_via_checkpoint(old_trainer, new_trainer, params, opt_state=None,
                           carry: Optional[CarryState] = None, *,
                           directory: Optional[str] = None,
                           step: int = 0,
                           async_write: bool = True):
    """Checkpoint round-trip migration: the fallback when the old mesh's
    buffers are actually gone (real node failure), and the equivalence
    oracle the in-memory path is asserted against — both produce bitwise
    identical state, this one at the price of a serialize/compress/disk
    round trip.  Writes through the async :class:`~repro.runtime.checkpoint.
    CheckpointWriter` by default (``async_write=False`` is the synchronous
    escape hatch — byte-identical output either way)."""
    t0 = time.perf_counter()
    spec = diff_plans(old_trainer.plan, new_trainer.plan)
    canon_p, canon_o = canonical_state(old_trainer, params, opt_state)
    tmp = None
    if directory is None:
        tmp = tempfile.TemporaryDirectory(prefix="resize-ckpt-")
        directory = tmp.name
    try:
        if async_write:
            with ckpt_lib.CheckpointWriter() as writer:
                writer.save_async(pathlib.Path(directory), step, canon_p,
                                  canon_o, old_trainer.plan)
                writer.wait()
        else:
            ckpt_lib.save(pathlib.Path(directory), step, canon_p, canon_o,
                          old_trainer.plan)
        restored = ckpt_lib.restore(pathlib.Path(directory), step,
                                    params_like=canon_p, opt_like=canon_o)
        new_p = new_trainer.place_params(restored["params"])
        new_o = None
        if canon_o is not None:
            new_o = new_trainer.place_opt_state(restored["opt"])
        _block(new_p, new_o)
    finally:
        if tmp is not None:
            tmp.cleanup()
    new_carry = carry.carried() if carry is not None else None
    report = MigrationReport(spec=spec, seconds=time.perf_counter() - t0,
                             bytes_moved=_tree_bytes(new_p, new_o),
                             path="checkpoint")
    return new_p, new_o, new_carry, report
