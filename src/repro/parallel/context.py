"""Context parallelism: ring flash-attention over the ``cp`` mesh axis.

Megatron-SP (``LayerStrategy.sp``) shards the sequence only in the *boundary*
region between blocks; inside attention every device still holds the full
sequence, so activation memory per device floors at O(S).  Context parallelism
shards the sequence *through* attention: each of ``cp`` devices keeps a
``S/cp`` query shard, and the k/v blocks rotate around a ring via
collective-permute while the online-softmax running ``(o, m, l)`` accumulators
merge the partial attention results block-by-block — the same merge the Pallas
flash kernel performs across its kv grid, lifted to the device level.

Sequence split is **zig-zag / load-balanced**: the sequence is cut into
``2·cp`` chunks and rank ``r`` holds chunks ``r`` and ``2·cp-1-r``.  Under
causal masking a contiguous split leaves the low ranks idle for most ring
steps (their kv blocks are in everyone's past, their q blocks see almost
nothing); the zig-zag pairing gives every rank one early and one late chunk so
each ring step carries ~half-visible blocks on every device.  Masking is
positional (global position arrays travel the ring with k/v), so the math is
exact for any layout.  ``S % (2·cp) == 0`` is required — odd remainders are
rejected, matching the search-side ``validate_cp`` gate.

Three lowerings:

* **serial reference** (``mesh=None``) — the explicit-``cp``-dim loop in pure
  jnp with ``jnp.roll`` as the ring step.  This is the CPU/interpret-mode
  numerical oracle and the path the grad-equivalence tests pin.
* **pure GSPMD** (default under a mesh) — same
  explicit-dim formulation with the leading ``cp`` dim sharding-constrained
  onto the ``cp`` mesh axis; ``jnp.roll`` on that dim lowers to the same
  collective-permute a manual ring would issue.  This also composes inside
  the pipeline's shard_map body (cp stays an auto axis there).
* **partial-auto shard_map** (``lowering="shard_map"``) — the ``cp`` axis is
  manual inside the body (``jax.lax.ppermute`` rotates k/v/positions), the
  remaining mesh axes stay auto so DP batch sharding and Megatron TP keep
  working inside.  Opt-in: on-TPU it is the lowering that pins the ring onto
  neighbor links.

``use_flash=True`` computes each ring step's partial with the Pallas flash
kernel (positional masking + ``return_residuals=True``) and merges the
normalized partials with :func:`merge_partials` — forward-only (the Pallas
kernel has no VJP of its own); training uses the differentiable jnp partials
under ``jax.checkpoint`` so the backward recomputes blocks flash-style.
"""
from __future__ import annotations

import functools
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import compat
from repro.compat import Mesh, NamedSharding, P

NEG_INF = -0.7 * float(np.finfo(np.float32).max)


# --------------------------------------------------------------------------
# zig-zag layout
# --------------------------------------------------------------------------

def validate_cp(seq_len: int, cp: int) -> None:
    """Gate shared by the search engine and the runtime: a cp degree is
    realizable iff the sequence splits into 2·cp equal zig-zag chunks
    (the same predicate the static verifier checks as GALV010)."""
    from repro.analysis.invariants import cp_seq_divisible

    if cp < 1:
        raise ValueError(f"cp must be >= 1, got {cp}")
    if not cp_seq_divisible(seq_len, cp):
        raise ValueError(
            f"context parallelism needs seq_len % (2*cp) == 0 for the "
            f"zig-zag split; got seq_len={seq_len}, cp={cp}")


def zigzag_permutation(seq_len: int, cp: int) -> np.ndarray:
    """Gather indices putting the sequence in zig-zag order: position block
    ``r`` (length S/cp) holds chunks ``r`` and ``2·cp-1-r`` of the natural
    order, so contiguous S/cp shards are the balanced rank assignments."""
    validate_cp(seq_len, cp)
    c = seq_len // (2 * cp)
    chunks = []
    for r in range(cp):
        chunks.append(np.arange(r * c, (r + 1) * c))
        chunks.append(np.arange((2 * cp - 1 - r) * c, (2 * cp - r) * c))
    return np.concatenate(chunks)


def inverse_permutation(perm: np.ndarray) -> np.ndarray:
    inv = np.empty_like(perm)
    inv[perm] = np.arange(len(perm))
    return inv


# --------------------------------------------------------------------------
# online-softmax partials
# --------------------------------------------------------------------------

def merge_partials(o1, m1, l1, o2, m2, l2):
    """Merge two *normalized* flash partials (o_i = acc_i / l_i with softmax
    stats m_i, l_i) — the device-level analogue of the kernel's kv-grid merge.
    Shapes: o (…, hd), m/l (…)."""
    m = jnp.maximum(m1, m2)
    a = l1 * jnp.exp(m1 - m)
    b = l2 * jnp.exp(m2 - m)
    l = a + b
    safe = jnp.maximum(l, 1e-30)
    o = (o1 * a[..., None] + o2 * b[..., None]) / safe[..., None]
    return o, m, l


# --------------------------------------------------------------------------
# ring cores
# --------------------------------------------------------------------------

def _ring_merge_loop(step_partial: Callable, permute: Callable, cp: int,
                     k, v, k_pos):
    """The ring protocol, once: rotate (k, v, k_pos) ``cp-1`` times with
    ``permute``, merging each step's normalized partial into the running
    (o, m, l) accumulators.  ``step_partial(k, v, k_pos) -> (o, m, l)`` with
    o normalized fp32 (…, Sq, hd) and m/l fp32 (…, Sq) — every lowering and
    per-step backend (jnp block math, Pallas kernel residuals) plugs in
    here, so protocol changes land exactly once."""
    o = m = l = None
    k_cur, v_cur, kp_cur = k, v, k_pos
    for t in range(cp):
        ob, mb, lb = step_partial(k_cur, v_cur, kp_cur)
        if o is None:
            o, m, l = ob, mb, lb
        else:
            o, m, l = merge_partials(o, m, l, ob, mb, lb)
        if t != cp - 1:
            k_cur, v_cur = permute(k_cur), permute(v_cur)
            kp_cur = permute(kp_cur)
    return o


def _block_partial(q, k, v, q_pos, k_pos, *, causal: bool):
    """Normalized jnp attention partial over one k/v block.  Shapes carry an
    arbitrary leading batch prefix: q/k/v (..., S, H, hd), positions
    broadcastable to (..., S).  Returns (o (…, H, Sq, hd), m, l (…, H, Sq)),
    all fp32 — the differentiable counterpart of the Pallas kernel's
    ``return_residuals`` output."""
    hd = q.shape[-1]
    s = jnp.einsum("...qhd,...shd->...hqs", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * hd ** -0.5
    if causal:
        mask = k_pos[..., None, :] <= q_pos[..., :, None]       # (..., Sq, Sk)
        s = jnp.where(mask[..., None, :, :], s, NEG_INF)
    m = jnp.max(s, axis=-1)
    p = jnp.exp(s - m[..., None])
    l = jnp.sum(p, axis=-1)
    o = jnp.einsum("...hqs,...shd->...hqd", p.astype(v.dtype),
                   v).astype(jnp.float32) / jnp.maximum(l, 1e-30)[..., None]
    return o, m, l


def _ring_explicit(qz, kz, vz, q_pos, k_pos, *, causal: bool,
                   constrain: Callable = lambda a: a):
    """Explicit-cp-dim ring: leaves (cp, B, Sc, H, hd), positions (cp, Sc).
    ``jnp.roll`` on dim 0 is the ring step (lowers to collective-permute when
    dim 0 is sharding-constrained onto the cp mesh axis)."""
    cp = qz.shape[0]

    def partial(k, v, kp):
        # positions broadcast over the B dim: (cp, Sc) -> (cp, 1, Sc)
        return _block_partial(qz, k, v, q_pos[:, None], kp[:, None],
                              causal=causal)

    permute = lambda a: constrain(jnp.roll(a, 1, axis=0))
    o = _ring_merge_loop(partial, permute, cp, kz, vz, k_pos)
    return jnp.moveaxis(o, 2, 3).astype(qz.dtype)               # (cp,B,Sc,H,hd)


def _ring_local(q, k, v, q_pos, k_pos, *, causal: bool, cp: int,
                permute: Callable, use_flash: bool = False,
                interpret: bool = False):
    """Per-device ring body (shard_map lowering): leaves (B, Sc, H, hd),
    positions (Sc,) or (B, Sc).  ``permute`` rotates a block to the next
    rank."""
    B, Sc, H, hd = q.shape
    if use_flash:
        from repro.kernels.flash_attention.kernel import flash_attention_fwd

        def partial(kb, vb, kp):
            ob, mb, lb = flash_attention_fwd(
                q, kb, vb, causal=causal,
                q_pos=jnp.broadcast_to(q_pos, (B, Sc)),
                k_pos=jnp.broadcast_to(kp, (B, Sc)),
                return_residuals=True, interpret=interpret)
            return jnp.moveaxis(ob, 1, 2).astype(jnp.float32), mb, lb
    else:
        def partial(kb, vb, kp):
            # positions broadcast over B (and H inside _block_partial)
            return _block_partial(q, kb, vb, jnp.broadcast_to(q_pos, (B, Sc)),
                                  jnp.broadcast_to(kp, (B, Sc)), causal=causal)

    o = _ring_merge_loop(partial, permute, cp, k, v, k_pos)
    return jnp.moveaxis(o, 1, 2).astype(q.dtype)                # (B,Sc,H,hd)


# --------------------------------------------------------------------------
# lowerings
# --------------------------------------------------------------------------

def _ring_shard_map(qz, kz, vz, pos, *, causal, mesh, axis, use_flash,
                    interpret):
    """Partial-auto shard_map lowering: cp manual (ppermute ring), other axes
    auto so TP head sharding / DP batch sharding keep working inside."""
    cp = mesh.shape[axis]
    ring = [(i, (i + 1) % cp) for i in range(cp)]

    def body(q_l, k_l, v_l, pos_l):
        qp = pos_l[0]
        permute = lambda a: jax.lax.ppermute(a, axis, ring)
        return _ring_local(q_l, k_l, v_l, qp, qp, causal=causal, cp=cp,
                           permute=permute, use_flash=use_flash,
                           interpret=interpret)

    return compat.shard_map(
        body, mesh=mesh,
        in_specs=(P(None, axis), P(None, axis), P(None, axis), P(axis)),
        out_specs=P(None, axis),
        axis_names={axis}, check_vma=False,
    )(qz, kz, vz, pos)


def _ring_gspmd(qz, kz, vz, pos, *, causal, mesh, axis):
    """Explicit-dim lowering: the cp dim stays a real array dim, constrained
    onto the cp mesh axis, and ``jnp.roll`` is the ring permute."""
    B, S, H, hd = qz.shape
    cp = mesh.shape[axis]
    Sc = S // cp
    sharding = NamedSharding(mesh, P(axis))
    constrain = lambda a: jax.lax.with_sharding_constraint(a, sharding)

    def to_cp(a):
        return constrain(jnp.moveaxis(a.reshape(B, cp, Sc, H, hd), 1, 0))

    out = _ring_explicit(to_cp(qz), to_cp(kz), to_cp(vz),
                         pos, pos, causal=causal, constrain=constrain)
    return jnp.moveaxis(out, 0, 1).reshape(B, S, H, hd)


def ring_attention(
    q, k, v,                       # (B, S, H, hd), equal head counts
    *,
    causal: bool = True,
    mesh: Optional[Mesh] = None,
    axis: str = "cp",
    cp: Optional[int] = None,      # required when mesh is None
    use_flash: bool = False,       # Pallas partials (forward-only)
    interpret: bool = False,
    lowering: Optional[str] = None,   # None/"gspmd" | "shard_map"
) -> jnp.ndarray:
    """Ring flash-attention over ``cp`` sequence shards; returns (B,S,H,hd).

    Inputs/outputs are in natural sequence order — the zig-zag permutation is
    applied (and inverted) internally.  Training paths should wrap the call in
    ``jax.checkpoint`` so the backward recomputes ring blocks flash-style
    instead of saving per-step probability blocks.
    """
    B, S, H, hd = q.shape
    if mesh is not None:
        cp = int(mesh.shape[axis])
    if cp is None:
        raise ValueError("ring_attention needs mesh= or cp=")
    validate_cp(S, cp)
    perm = zigzag_permutation(S, cp)
    pos = jnp.asarray(perm, jnp.int32).reshape(cp, S // cp)
    qz, kz, vz = (_permute_seq(a, perm) for a in (q, k, v))

    if mesh is None:
        Sc = S // cp
        if use_flash:
            out = _serial_flash_ring(qz, kz, vz, pos, causal, cp,
                                     interpret=interpret)
        else:
            to_cp = lambda a: jnp.moveaxis(a.reshape(B, cp, Sc, H, hd), 1, 0)
            out = _ring_explicit(to_cp(qz), to_cp(kz), to_cp(vz),
                                 pos, pos, causal=causal)
            out = jnp.moveaxis(out, 0, 1).reshape(B, S, H, hd)
    elif lowering == "shard_map":
        out = _ring_shard_map(qz, kz, vz, pos, causal=causal, mesh=mesh,
                              axis=axis, use_flash=use_flash,
                              interpret=interpret)
    else:
        out = _ring_gspmd(qz, kz, vz, pos, causal=causal, mesh=mesh, axis=axis)
    return _permute_seq(out, inverse_permutation(perm))


def _permute_seq(x, perm: np.ndarray):
    """``x[:, perm]`` for a static permutation made of contiguous runs, as
    slices and one concatenate.  A gather over the cp-sharded sequence dim
    inside the pipeline's shard_map body check-fails XLA's SPMD partitioner
    (``ExpandDeviceGroupsWithIota``); slices partition cleanly."""
    cuts = [0] + [i for i in range(1, len(perm)) if perm[i] != perm[i - 1] + 1]
    runs = zip(cuts, cuts[1:] + [len(perm)])
    return jnp.concatenate(
        [jax.lax.slice_in_dim(x, int(perm[a]), int(perm[a]) + b - a, axis=1)
         for a, b in runs], axis=1)


def _serial_flash_ring(qz, kz, vz, pos, causal, cp, *, interpret):
    """Single-device ring over Pallas-kernel partials: cp folds into the
    kernel's batch dim, positions vary per row (forward-only oracle for the
    kernel-residual merge path)."""
    B, S, H, hd = qz.shape
    Sc = S // cp
    fold = lambda a: jnp.moveaxis(
        a.reshape(B, cp, Sc, H, hd), 1, 0).reshape(cp * B, Sc, H, hd)
    qf, kf, vf = fold(qz), fold(kz), fold(vz)
    qp = jnp.repeat(pos, B, axis=0)                             # (cp*B, Sc)
    out = _ring_local(qf, kf, vf, qp, qp, causal=causal, cp=cp,
                      permute=functools.partial(_fold_roll, cp=cp, B=B),
                      use_flash=True, interpret=interpret)
    return jnp.moveaxis(out.reshape(cp, B, Sc, H, hd), 0, 1).reshape(qz.shape)


def _fold_roll(a, *, cp: int, B: int):
    """Roll the cp component of a (cp·B, ...) folded leading dim by one."""
    b = a.reshape((cp, B) + a.shape[1:])
    return jnp.roll(b, 1, axis=0).reshape(a.shape)
