"""Blocked flash-attention forward kernel (Pallas, TPU target).

Design for the TPU memory hierarchy (DESIGN.md §7):
  * grid = (batch·heads, q-blocks, kv-blocks); the kv dim is the innermost,
    sequential ("arbitrary") dimension so the online-softmax state lives in
    VMEM scratch across kv steps.
  * BlockSpecs tile q/k/v into (block, head_dim) VMEM windows — block=128
    keeps the s = q·kᵀ matmul MXU-shaped (128×128 systolic array) and the
    working set (3·128·hd·2B + scratch) well under VMEM.
  * accumulators (o, m, l) are fp32 scratch; inputs stay bf16 on the MXU.
  * causal masking is positional (iota over the block offsets); fully-masked
    blocks still run — a future hillclimb can skip them by shrinking the kv
    grid per q block (§Perf notes).

Context-parallel extensions (parallel/context.py rides these):
  * ``q_pos``/``k_pos`` (B, S) int32 — explicit global positions replacing the
    iota offsets in the causal mask, so a zig-zag sequence shard (whose local
    rows are non-contiguous in global positions) masks exactly.
  * ``return_residuals=True`` — also emit the softmax stats (m, l) per row,
    letting ring attention merge partial results from different kv shards with
    the same online-softmax merge the kernel itself runs across its kv grid.

Validated with ``interpret=True`` on CPU against ``ref.attention_reference``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -0.7 * float(jnp.finfo(jnp.float32).max)

DEFAULT_BLOCK_Q = 128
DEFAULT_BLOCK_KV = 128


def _flash_fwd_kernel(*refs, causal: bool, positional: bool, residuals: bool,
                      block_q: int, block_kv: int,
                      num_kv_blocks: int, scale: float):
    refs = list(refs)
    q_ref, k_ref, v_ref = refs[:3]
    refs = refs[3:]
    if positional:
        qp_ref, kp_ref = refs[:2]
        refs = refs[2:]
    o_ref = refs[0]
    refs = refs[1:]
    if residuals:
        m_out, l_out = refs[:2]
        refs = refs[2:]
    acc_ref, m_ref, l_ref = refs

    qi = pl.program_id(1)
    kj = pl.program_id(2)

    @pl.when(kj == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0].astype(jnp.float32)                   # (bq, hd)
    k = k_ref[0].astype(jnp.float32)                   # (bk, hd)
    v = v_ref[0].astype(jnp.float32)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale

    if causal:
        if positional:
            q_pos = qp_ref[0]                          # (bq, 1) global positions
            k_pos = kp_ref[0]                          # (1, bk)
        else:
            q_pos = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_kv), 0)
            k_pos = kj * block_kv + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_kv), 1)
        s = jnp.where(k_pos <= q_pos, s, NEG_INF)

    m_prev = m_ref[...]
    l_prev = l_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
    p = jnp.exp(s - m_new[:, None])
    corr = jnp.exp(m_prev - m_new)
    l_new = l_prev * corr + jnp.sum(p, axis=-1)
    acc_ref[...] = acc_ref[...] * corr[:, None] + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_ref[...] = m_new
    l_ref[...] = l_new

    @pl.when(kj == num_kv_blocks - 1)
    def _finalize():
        o_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)[:, None]).astype(o_ref.dtype)
        if residuals:
            m_out[0] = m_ref[...][None, :]
            l_out[0] = l_ref[...][None, :]


def _tile_positions(pos, B: int, H: int, S: int):
    """(S,) or (B, S) int32 positions -> (B·H, S) matching the kernel grid."""
    pos = jnp.asarray(pos, jnp.int32)
    if pos.ndim == 1:
        pos = jnp.broadcast_to(pos[None], (B, S))
    return jnp.broadcast_to(pos[:, None, :], (B, H, S)).reshape(B * H, S)


def flash_attention_fwd(q, k, v, *, causal: bool = True,
                        q_pos=None, k_pos=None,
                        return_residuals: bool = False,
                        block_q: int = DEFAULT_BLOCK_Q,
                        block_kv: int = DEFAULT_BLOCK_KV,
                        interpret: bool = False):
    """q/k/v: (B, S, H, hd) with equal head counts -> (B, S, H, hd).

    ``q_pos``/``k_pos`` ((S,) or (B, S) int32) switch the causal mask to
    explicit global positions (context-parallel zig-zag shards).  With
    ``return_residuals`` the result is ``(out, m, l)`` with m/l (B, H, S)
    fp32 softmax stats for partial-result merging.
    """
    B, Sq, H, hd = q.shape
    Sk = k.shape[1]
    # shrink blocks to divisors (ring shards hand in seq/cp slices that need
    # not be 128-multiples); same degradation rule as chunked_attention
    block_q = min(block_q, Sq)
    block_kv = min(block_kv, Sk)
    while Sq % block_q:
        block_q //= 2
    while Sk % block_kv:
        block_kv //= 2
    assert Sq % block_q == 0 and Sk % block_kv == 0, (Sq, Sk, block_q, block_kv)
    nq, nk = Sq // block_q, Sk // block_kv
    positional = causal and q_pos is not None
    if positional:
        assert k_pos is not None, "q_pos requires k_pos"

    # (B, S, H, hd) -> (B*H, S, hd): one grid row per (batch, head)
    qt = q.transpose(0, 2, 1, 3).reshape(B * H, Sq, hd)
    kt = k.transpose(0, 2, 1, 3).reshape(B * H, Sk, hd)
    vt = v.transpose(0, 2, 1, 3).reshape(B * H, Sk, hd)

    kernel = functools.partial(
        _flash_fwd_kernel, causal=causal, positional=positional,
        residuals=return_residuals, block_q=block_q, block_kv=block_kv,
        num_kv_blocks=nk, scale=hd ** -0.5)

    in_specs = [
        pl.BlockSpec((1, block_q, hd), lambda b, i, j: (b, i, 0)),
        pl.BlockSpec((1, block_kv, hd), lambda b, i, j: (b, j, 0)),
        pl.BlockSpec((1, block_kv, hd), lambda b, i, j: (b, j, 0)),
    ]
    inputs = [qt, kt, vt]
    if positional:
        # The TPU tiling rule applies to a block's last two dims: each must
        # be a multiple of (8, 128) or span the array.  A unit dim spanning
        # the array satisfies it, and puts q positions on sublanes and k
        # positions on lanes, as the (bq, bk) mask wants them.
        in_specs += [
            pl.BlockSpec((1, block_q, 1), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, 1, block_kv), lambda b, i, j: (b, 0, j)),
        ]
        inputs += [_tile_positions(q_pos, B, H, Sq)[:, :, None],
                   _tile_positions(k_pos, B, H, Sk)[:, None, :]]

    out_specs = pl.BlockSpec((1, block_q, hd), lambda b, i, j: (b, i, 0))
    out_shape = jax.ShapeDtypeStruct((B * H, Sq, hd), q.dtype)
    if return_residuals:
        # (B·H, 1, Sq): the unit dim spans the array (tiling rule above)
        stat_spec = pl.BlockSpec((1, 1, block_q), lambda b, i, j: (b, 0, i))
        out_specs = [out_specs, stat_spec, stat_spec]
        out_shape = [out_shape,
                     jax.ShapeDtypeStruct((B * H, 1, Sq), jnp.float32),
                     jax.ShapeDtypeStruct((B * H, 1, Sq), jnp.float32)]

    out = pl.pallas_call(
        kernel,
        grid=(B * H, nq, nk),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[
            pltpu.VMEM((block_q, hd), jnp.float32),    # acc
            pltpu.VMEM((block_q,), jnp.float32),       # m (running max)
            pltpu.VMEM((block_q,), jnp.float32),       # l (running denom)
        ],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ) if not interpret else None,
    )(*inputs)
    if return_residuals:
        o, m, l = out
        return (o.reshape(B, H, Sq, hd).transpose(0, 2, 1, 3),
                m.reshape(B, H, Sq), l.reshape(B, H, Sq))
    return out.reshape(B, H, Sq, hd).transpose(0, 2, 1, 3)
