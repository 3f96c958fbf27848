"""Schedule-aware pipeline parallelism (one lowering, three schedules).

The pipeline ("pod") axis is *manual*: activations move stage→stage with a
collective permute.  The remaining mesh axes ("data", "model") stay *auto*,
so inside a stage the usual GSPMD sharding constraints (DP batch sharding,
Megatron TP, ZeRO) keep working — this is the TPU-native mapping of
Galvatron's "PP outermost, across the slowest links" decision-tree take-away
(DESIGN.md §2): cross-pod links are the slowest, PP traffic is the smallest.

The lowering is a partial-auto ``shard_map``: the pod axis is manual inside
the body (``jax.lax.ppermute`` moves activations), other axes stay auto.

The tick loop runs ``M + S - 1`` steps (M microbatches, S stages); jax
autodiff reverses the schedule for the backward pass automatically (the
transpose of a permute is the reverse permute), reproducing GPipe's
fwd-then-bwd bubble shape.  Idle stages compute on garbage inputs — exactly
the (S-1)/(M+S-1) bubble the cost model charges for PP.

Schedules (``ExecutionPlan.pp_schedule``), all numerically equivalent:

* **gpipe** — one tick loop over all M microbatches; every microbatch's
  activations are live when the backward starts (M in flight per stage).
* **1f1b** — the same tick loop applied to *windows* of S microbatches with
  gradient accumulation across windows (driven by
  ``runtime/train_pp.PipelineTrainer``): each window's backward runs before
  the next window's forward, so at most min(S, M) microbatch activations are
  live per stage — the 1F1B memory bound.  ``pipeline_forward`` itself sees
  one window at a time.
* **interleaved** — each physical stage holds ``v`` non-contiguous layer
  chunks (``stage_stack(..., interleave=v)`` lays chunk ``j·S + s`` at
  ``[s, j]``); activations traverse the physical ring v times, one chained
  tick-loop pass per virtual round, stage s applying chunk ``j·S + s`` in
  pass j.  This pass-sequential lowering keeps the math and p2p hop count of
  the interleaved schedule; the 1/v bubble shrink the cost model charges is
  a property of the target-hardware schedule, where pass j+1's warm-up
  overlaps pass j's tail (the CPU tick loop, like GPipe's garbage lanes,
  does not try to reproduce the wall-clock overlap).
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp

from repro import compat
from repro.compat import Mesh, P

#: Stage-boundary activation dtype.  Kept fp32: the backward pass psums the
#: input cotangent over the pipe axis, and a bf16 all-reduce trips an XLA-CPU
#: AllReducePromotion crash (and loses precision on real hardware anyway).
#: The cost model charges ``PIPELINE_BOUNDARY_BYTES_PER_ELEM`` per element
#: for boundary p2p — the plan verifier (GALV040) asserts the two agree.
BOUNDARY_DTYPE = jnp.float32


def pipeline_forward(
    stage_params,                  # pytree, leaves (S, Lps, ...) — dim0 sharded on axis
    x_micro: jnp.ndarray,          # (M, mb, seq, D) microbatched activations
    stage_fn: Callable,            # (local_params, (mb,seq,D)) -> (mb,seq,D)
    *,
    mesh: Mesh,
    axis: str = "pod",
    schedule: str = "gpipe",       # gpipe | 1f1b | interleaved
    num_virtual: int = 1,          # virtual stages per physical stage (interleaved)
) -> jnp.ndarray:
    """Returns (M, mb, seq, D) outputs of the final (virtual) stage.

    The stage boundary is kept fp32: the backward pass psums the input
    cotangent over the pipe axis, and a bf16 all-reduce trips an XLA-CPU
    AllReducePromotion crash (and loses precision on real hardware anyway).
    ``stage_fn`` should cast to bf16 internally for compute.

    ``schedule="1f1b"`` runs the same tick loop as gpipe — the 1F1B memory
    bound comes from the caller feeding one S-microbatch window per call and
    accumulating gradients across windows (see the module docstring).
    ``schedule="interleaved"`` expects ``stage_params`` leaves shaped
    ``(S, num_virtual, Lc, ...)`` from ``stage_stack(..., interleave=v)`` and
    chains one tick-loop pass per virtual round.
    """
    if schedule == "interleaved" and num_virtual > 1:
        h = x_micro
        for j in range(num_virtual):
            chunk = jax.tree.map(lambda a, j=j: a[:, j], stage_params)
            h = _forward_round(chunk, h, stage_fn, mesh=mesh, axis=axis)
        return h
    return _forward_round(stage_params, x_micro, stage_fn, mesh=mesh, axis=axis)


def _forward_round(stage_params, x_micro, stage_fn, *, mesh, axis):
    """One full traversal of the physical ring."""
    S = mesh.shape[axis]
    M = x_micro.shape[0]
    in_dtype = x_micro.dtype
    x_micro = x_micro.astype(BOUNDARY_DTYPE)

    def body(local_params, xm):
        # local_params leaves: (1, Lps, ...) — this stage's slice
        local = jax.tree.map(lambda a: a[0], local_params)
        stage = jax.lax.axis_index(axis)
        is_first = stage == 0
        is_last = stage == S - 1
        fwd_perm = [(i, i + 1) for i in range(S - 1)]

        def tick(carry, t):
            recv, outs = carry
            mb_idx = jnp.clip(t - 0, 0, M - 1)
            feed = jnp.where(is_first & (t < M), 1.0, 0.0)
            inp = feed * xm[mb_idx] + (1.0 - feed) * recv
            h = stage_fn(local, inp.astype(in_dtype)).astype(BOUNDARY_DTYPE)
            out_idx = jnp.clip(t - (S - 1), 0, M - 1)
            write = is_last & (t >= S - 1) & (t - (S - 1) < M)
            outs = jax.lax.dynamic_update_index_in_dim(
                outs, jnp.where(write, h, outs[out_idx]), out_idx, 0)
            recv_next = jax.lax.ppermute(h, axis, fwd_perm)
            return (recv_next, outs), None

        outs0 = jnp.zeros_like(xm)
        recv0 = jnp.zeros_like(xm[0])
        (_, outs), _ = jax.lax.scan(tick, (recv0, outs0), jnp.arange(M + S - 1))
        # emit per-stage outputs; only the last stage's slice is meaningful —
        # the caller takes [-1], avoiding a full-activation psum over the pipe
        # axis (which also trips an XLA-CPU AllReducePromotion bug on bf16).
        return outs[None]

    staged = compat.shard_map(
        body,
        mesh=mesh,
        in_specs=(P(axis), P()),
        out_specs=P(axis),
        axis_names={axis},
        check_vma=False,
    )(stage_params, x_micro)
    return staged[-1]


def stage_stack(blocks, num_stages: int, interleave: int = 1):
    """Reshape stacked layer params (L, ...) -> (S, L/S, ...), or with
    ``interleave=v`` -> (S, v, L/(S·v), ...) where layer chunk ``c = j·S + s``
    (the Megatron interleaved assignment: stage s holds chunks s, S+s, 2S+s,
    ...) lands at ``[s, j]``.  Dim 0 stays the pipe axis either way, so the
    staged sharding specs are interleave-agnostic beyond an extra None."""
    def r(a):
        L = a.shape[0]
        assert L % (num_stages * interleave) == 0, (L, num_stages, interleave)
        if interleave == 1:
            return a.reshape((num_stages, L // num_stages) + a.shape[1:])
        chunk = L // (num_stages * interleave)
        b = a.reshape((interleave, num_stages, chunk) + a.shape[1:])
        return jnp.swapaxes(b, 0, 1)

    return jax.tree.map(r, blocks)


def unstage_stack(blocks, interleave: int = 1):
    def u(a):
        if interleave == 1:
            return a.reshape((-1,) + a.shape[2:])
        b = jnp.swapaxes(a, 0, 1)            # (v, S, Lc, ...) — chunk-major
        return b.reshape((-1,) + b.shape[3:])

    return jax.tree.map(u, blocks)
