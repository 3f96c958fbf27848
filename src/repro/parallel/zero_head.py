"""The LM head under ZeRO-3, with its weight's gather and its gradient's
reduce-scatter hidden behind the head's own matmuls.

Under a ZeRO-3 plan the head weight (``embed.head`` (d, V), or the tied
``embed.tok`` (V, d)) has its contracting dim ``d`` sharded over the
data-parallel mesh axes and its vocabulary over the tensor-parallel axis.
Left to GSPMD, the head gathers each device's whole vocabulary slice in
one all-gather right before its forward matmul, and reduce-scatters the
whole weight gradient in one op between the dW and dX matmuls; neither has
independent work beside it, so both run exposed.

:func:`gathered_logits` splits each device's own vocabulary slice into
``k`` chunks (no data moves between the tensor-parallel shards) inside a
``shard_map`` that is manual over those axes.  Chunk ``i + 1``'s gather can
then run under chunk ``i``'s matmul.  Its ``custom_vjp`` reduce-scatters
chunk ``i``'s weight gradient to the ZeRO layout as soon as that chunk's
dW exists, as a ring of collective-permutes: XLA:TPU runs those
asynchronously, under the later chunks' dW and the dX matmuls, where it
runs a reduce-scatter as a synchronous op.  The ring sends what a
reduce-scatter sends, ``(n - 1) / n`` of each chunk a device.

The work is the plain einsum's: bf16 operands, each logit one dot product
over all of ``d``, the bf16 product cast to fp32, the weight gradient
reduced in bf16.  The gathered chunks are kept for the backward's dX, as
GSPMD keeps the gathered weight, so nothing is gathered twice.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp

from repro import compat
from repro.compat import Mesh, P

#: Most chunks a device's vocabulary slice is cut into.
MAX_CHUNKS = 4


@dataclasses.dataclass(frozen=True)
class ZeroHead:
    """Mesh axes of a ZeRO-3 head weight: ``zero_axes`` shard its
    contracting dim ``d``, ``vocab_axes`` (possibly none) its vocabulary."""

    mesh: Mesh
    zero_axes: tuple[str, ...]
    vocab_axes: tuple[str, ...]

    def size(self, axes: tuple[str, ...]) -> int:
        return math.prod(self.mesh.shape[a] for a in axes)


def _axes(entry) -> tuple[str, ...]:
    if entry is None:
        return ()
    return tuple(entry) if isinstance(entry, tuple) else (entry,)


def zero_head(param_specs: dict, mesh: Optional[Mesh]) -> Optional[ZeroHead]:
    """The head weight's layout from the plan's parameter specs, or None
    where its contracting dim is not sharded over mesh axes of more than
    one device (no mesh, ZeRO below 3, one data-parallel rank)."""
    embed = param_specs.get("embed", {})
    if mesh is None or not ("head" in embed or "tok" in embed):
        return None
    # (d, V) untied, (V, d) tied
    spec, d_dim = (embed["head"], 0) if "head" in embed else (embed["tok"], 1)
    entries = tuple(spec) + (None,) * (2 - len(spec))
    zh = ZeroHead(mesh, _axes(entries[d_dim]), _axes(entries[1 - d_dim]))
    return zh if zh.size(zh.zero_axes) > 1 else None


def head_chunks(vocab_local: int) -> int:
    """Largest chunk count, at most :data:`MAX_CHUNKS`, that divides a
    device's vocabulary slice."""
    return max(k for k in range(1, MAX_CHUNKS + 1) if vocab_local % k == 0)


def chunks_for(zh: Optional[ZeroHead], x_shape, vocab: int) -> int:
    """Chunks of the gathered head for activations ``x_shape`` (B, S, d)
    and a vocabulary of ``vocab``, or 0 where the plain einsum runs: no
    ZeRO-3 head, or a batch or sequence the head's axes do not divide."""
    if zh is None:
        return 0
    a, t = zh.size(zh.zero_axes), zh.size(zh.vocab_axes)
    if x_shape[0] % a or x_shape[1] % t or vocab % t:
        return 0
    return head_chunks(vocab // t)


def _entry(axes: tuple[str, ...]):
    """PartitionSpec entry for a tuple of mesh axes."""
    return None if not axes else axes[0] if len(axes) == 1 else axes


def _layout(zh: ZeroHead, vocab_dim: int):
    """(x spec, weight spec, gathered-chunk spec, logits spec)."""
    a, t = _entry(zh.zero_axes), _entry(zh.vocab_axes)
    if vocab_dim == 0:
        return P(a), P(t, a), P(t, None), P(a, None, t)
    return P(a), P(a, t), P(None, t), P(a, None, t)


def _shard_map(zh: ZeroHead, body, in_specs, out_specs):
    return compat.shard_map(body, mesh=zh.mesh, in_specs=in_specs,
                            out_specs=out_specs,
                            axis_names=set(zh.zero_axes + zh.vocab_axes),
                            check_vma=False)


def ring_reduce_scatter(a, axes: tuple[str, ...], n: int, dim: int):
    """``psum_scatter(a, axes, scatter_dimension=dim, tiled=True)`` over
    the ``n`` devices of ``axes``, as ``n - 1`` ring steps: each step
    passes a running block sum to the next rank, which adds its own part.
    Rank ``r`` ends with block ``r``, the sum over every rank."""
    r = jax.lax.axis_index(axes)
    blk = a.shape[dim] // n
    ring = [(i, (i + 1) % n) for i in range(n)]

    def block(s):
        return jax.lax.dynamic_slice_in_dim(a, ((r - 1 - s) % n) * blk, blk,
                                            axis=dim)

    acc = block(0)
    for s in range(1, n):
        acc = jax.lax.ppermute(acc, axes, ring) + block(s)
    return acc


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4))
def gathered_logits(x, w, zh: ZeroHead, k: int, vocab_dim: int):
    """fp32 logits (B, S, V) of bf16 ``x`` (B, S, d) and the ZeRO-3
    sharded bf16 head weight ``w`` ((d, V) for ``vocab_dim`` 1, the tied
    (V, d) for 0), in ``k`` chunks of each device's vocabulary slice."""
    return _forward(x, w, zh, k, vocab_dim)[0]


def _forward(x, w, zh, k, vocab_dim):
    x_spec, w_spec, chunk_spec, logits_spec = _layout(zh, vocab_dim)
    eq = "bsd,vd->bsv" if vocab_dim == 0 else "bsd,dv->bsv"

    def body(x_l, w_l):
        chunks = [jax.lax.all_gather(c, zh.zero_axes, axis=1 - vocab_dim,
                                     tiled=True)
                  for c in jnp.split(w_l, k, axis=vocab_dim)]
        logits = [jnp.einsum(eq, x_l, c).astype(jnp.float32) for c in chunks]
        return jnp.concatenate(logits, axis=-1), x_l, chunks

    return _shard_map(zh, body, (x_spec, w_spec),
                      (logits_spec, x_spec, [chunk_spec] * k))(x, w)


def _fwd(x, w, zh, k, vocab_dim):
    logits, x_full, chunks = _forward(x, w, zh, k, vocab_dim)
    return logits, (x_full, chunks)


def _bwd(zh, k, vocab_dim, res, g):
    x_full, chunks = res
    x_spec, w_spec, chunk_spec, logits_spec = _layout(zh, vocab_dim)
    dw_eq, dx_eq = (("bsd,bsv->vd", "bsv,vd->bsd") if vocab_dim == 0
                    else ("bsd,bsv->dv", "bsv,dv->bsd"))
    n = zh.size(zh.zero_axes)

    def body(x_l, g_l, chunks_l):
        g_l = g_l.astype(x_l.dtype)
        dws = [ring_reduce_scatter(jnp.einsum(dw_eq, x_l, g_c), zh.zero_axes,
                                   n, 1 - vocab_dim)
               for g_c in jnp.split(g_l, k, axis=-1)]
        dx = jnp.einsum(dx_eq, g_l, jnp.concatenate(chunks_l, axis=vocab_dim))
        if zh.vocab_axes:
            # each tensor-parallel rank holds a partial sum over its slice
            dx = jax.lax.psum_scatter(dx, zh.vocab_axes, scatter_dimension=1,
                                      tiled=True)
        return dx, jnp.concatenate(dws, axis=vocab_dim)

    return _shard_map(zh, body, (x_spec, logits_spec, [chunk_spec] * k),
                      (P(x_spec[0], _entry(zh.vocab_axes)), w_spec))(
                          x_full, g, chunks)


gathered_logits.defvjp(_fwd, _bwd)
