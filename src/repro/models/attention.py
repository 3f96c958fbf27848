"""Multi-head attention with GQA, qk-norm, optional bias, KV cache, cross-attn.

Head handling: K/V are stored compact (num_kv_heads) but *expanded* to the
query-head count before the attention math, and query heads are zero-PADDED
up to a multiple of the tensor-parallel shard size (taken from the active
axis rules).  Padded heads multiply zero rows of ``wo`` so they contribute
nothing; this keeps every sharded dim divisible, which ``jax.jit``
in/out-shardings require, at the cost of ceil()-rounded FLOPs that the
search engine's cost model accounts for.

Two reference paths:
  * dense grouped einsum (small sequences — exact, simple)
  * ``chunked_attention``: flash-style online-softmax double-scan over q/kv
    blocks in pure jnp — O(block²) live memory instead of O(S²).  This is
    also the numerical oracle for the Pallas flash kernel.
``impl="flash"`` selects the Pallas TPU kernel for long full-sequence passes.
"""
from __future__ import annotations

import os
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.registry import ModelConfig
from repro.models.common import ParamDef, scoped
from repro.models.norms import head_rmsnorm
from repro.models.rotary import apply_rope, rope_angles
from repro.parallel.axes import current_rules, lc, ring_context

NEG_INF = -0.7 * float(np.finfo(np.float32).max)
DENSE_MAX_SEQ = 2048          # above this, use the chunked (flash-style) path
CHUNK_Q = 1024
CHUNK_KV = 1024


def attn_defs(cfg: ModelConfig, cross: bool = False) -> dict:
    d, h, kv, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    # ParamDef's default fan-in heuristic (shape[-2]) is wrong for these 3-D
    # projections: q/k/v contract over d_model and wo over h·hd, so the std
    # must be set explicitly or q/k/v come out ~sqrt(d/kv)× too hot — enough
    # to blow up the residual stream through a shared attention block
    # (observed: zamba2 activations at 10× scale, grad norms at 300+, loss
    # oscillating under the clipped optimizer).
    defs = {
        "wq": ParamDef((d, h, hd), ("embed", "q_heads", "head_dim"), scale=d ** -0.5),
        "wk": ParamDef((d, kv, hd), ("embed", "kv_heads", "head_dim"), scale=d ** -0.5),
        "wv": ParamDef((d, kv, hd), ("embed", "kv_heads", "head_dim"), scale=d ** -0.5),
        "wo": ParamDef((h, hd, d), ("q_heads", "head_dim", "embed"), scale=(h * hd) ** -0.5),
    }
    if cfg.qkv_bias and not cross:
        defs["bq"] = ParamDef((h, hd), ("q_heads", "head_dim"), init="zeros")
        defs["bk"] = ParamDef((kv, hd), ("kv_heads", "head_dim"), init="zeros")
        defs["bv"] = ParamDef((kv, hd), ("kv_heads", "head_dim"), init="zeros")
    if cfg.qk_norm and not cross:
        defs["q_norm"] = ParamDef((hd,), ("head_dim",), init="ones")
        defs["k_norm"] = ParamDef((hd,), ("head_dim",), init="ones")
    return defs


# --------------------------------------------------------------------------
# head expansion / padding
# --------------------------------------------------------------------------

def _shard_size(logical: str) -> int:
    rules = current_rules()
    if rules is None or rules.mesh is None:
        return 1
    return rules.axis_size(logical)


def padded_head_count(num_heads: int) -> int:
    s = _shard_size("q_heads")
    return ((num_heads + s - 1) // s) * s


def _kv_expand_index(num_q: int, num_kv: int, padded: int) -> np.ndarray:
    """Map expanded/padded q-head index -> source kv head (pads map to 0)."""
    g = num_q // num_kv
    idx = np.arange(padded) // g
    idx[num_q:] = 0
    return np.minimum(idx, num_kv - 1)


def expand_and_pad(q, k, v):
    """q (B,Sq,H,hd), k/v (B,Sk,KV,hd) -> all (·,·,Hp,hd) with Hp % tp == 0."""
    H, KV = q.shape[2], k.shape[2]
    Hp = padded_head_count(H)
    if Hp != H:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, Hp - H), (0, 0)))
    if Hp == H == KV:            # MHA, no padding: skip the identity gather
        return q, k, v
    idx = jnp.asarray(_kv_expand_index(H, KV, Hp))
    k = jnp.take(k, idx, axis=2)
    v = jnp.take(v, idx, axis=2)
    return q, k, v


# --------------------------------------------------------------------------
# attention math (heads already expanded: q/k/v all (B,S,H,hd))
# --------------------------------------------------------------------------

def dense_attention(q, k, v, *, causal, q_offset=0, kv_len=None):
    B, Sq, H, hd = q.shape
    Sk = k.shape[1]
    scale = hd ** -0.5
    logits = jnp.einsum("bqhd,bshd->bhqs", q, k).astype(jnp.float32) * scale
    mask = jnp.ones((Sq, Sk), dtype=bool)
    if causal:
        qpos = q_offset + jnp.arange(Sq)[:, None]
        mask = jnp.arange(Sk)[None, :] <= qpos
    mask = jnp.broadcast_to(mask[None, None], (B, 1, Sq, Sk))
    if kv_len is not None:
        valid = jnp.arange(Sk)[None, :] < kv_len[:, None]
        mask = mask & valid[:, None, None, :]
    logits = jnp.where(mask, logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqs,bshd->bqhd", probs, v)


CAUSAL_SKIP = os.environ.get("REPRO_ATTN_CAUSAL_SKIP", "0") == "1"


def chunked_attention(q, k, v, *, causal, q_offset=0, kv_len=None,
                      chunk_q: int = CHUNK_Q, chunk_kv: int = CHUNK_KV,
                      causal_skip: Optional[bool] = None):
    """Flash-style online softmax; O(chunk_q·chunk_kv) live logits.

    ``causal_skip`` (§Perf beyond-paper optimization, default via
    REPRO_ATTN_CAUSAL_SKIP): iterate only the lower-triangular (q,kv) block
    pairs instead of the full nq×nk grid — the upper triangle is fully
    masked, so skipping it removes ~(nq-1)/(2nq) of the quadratic FLOPs
    (exactly what the TPU flash kernel's block-sparse iteration does).
    Requires a static q offset (training/prefill, not decode).
    """
    B, Sq, H, hd = q.shape
    Sk = k.shape[1]
    cq = min(chunk_q, Sq)
    ck = min(chunk_kv, Sk)
    while Sq % cq:
        cq //= 2
    while Sk % ck:
        ck //= 2
    nq, nk = Sq // cq, Sk // ck
    scale = hd ** -0.5
    if causal_skip is None:
        causal_skip = CAUSAL_SKIP
    causal_skip = (causal_skip and causal and isinstance(q_offset, int)
                   and q_offset == 0 and Sq == Sk and cq == ck)

    qc = jnp.moveaxis(q.reshape(B, nq, cq, H, hd), 1, 0)     # (nq,B,cq,H,hd)
    kc = jnp.moveaxis(k.reshape(B, nk, ck, H, hd), 1, 0)
    vc = jnp.moveaxis(v.reshape(B, nk, ck, H, hd), 1, 0)

    def kv_step(carry, j, qi, qpos):
        o, m, l = carry
        kj, vj = kc[j], vc[j]
        s = jnp.einsum("bqhd,bshd->bhqs", qi, kj).astype(jnp.float32) * scale
        kpos = j * ck + jnp.arange(ck)
        mask = jnp.ones((cq, ck), bool)
        if causal:
            mask = kpos[None, :] <= qpos[:, None]
        mask = jnp.broadcast_to(mask[None, None], (B, 1, cq, ck))
        if kv_len is not None:
            mask = mask & (kpos[None, :] < kv_len[:, None])[:, None, None, :]
        s = jnp.where(mask, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + jnp.sum(p, axis=-1)
        o_new = o * corr[..., None] + jnp.einsum(
            "bhqs,bshd->bhqd", p.astype(vj.dtype), vj).astype(jnp.float32)
        return (o_new, m_new, l_new), None

    def init():
        return (jnp.zeros((B, H, cq, hd), jnp.float32),
                jnp.full((B, H, cq), NEG_INF, jnp.float32),
                jnp.zeros((B, H, cq), jnp.float32))

    def finalize(o, m, l):
        return jnp.moveaxis(o / jnp.maximum(l[..., None], 1e-30), 1, 2)

    if causal_skip:
        # lower-triangular iteration: q block i only visits kv blocks 0..i
        outs = []
        for i in range(nq):
            qpos = q_offset + i * cq + jnp.arange(cq)
            (o, m, l), _ = jax.lax.scan(
                lambda c, j: kv_step(c, j, qc[i], qpos), init(), jnp.arange(i + 1))
            outs.append(finalize(o, m, l))
        out = jnp.stack(outs, 0)
    else:
        def q_block(i):
            qpos = q_offset + i * cq + jnp.arange(cq)
            (o, m, l), _ = jax.lax.scan(
                lambda c, j: kv_step(c, j, qc[i], qpos), init(), jnp.arange(nk))
            return finalize(o, m, l)

        out = jax.lax.map(q_block, jnp.arange(nq))
    out = jnp.moveaxis(out, 0, 1).reshape(B, Sq, H, hd)
    return out.astype(q.dtype)


def attention_math(q, k, v, *, causal, q_offset=0, kv_len=None, impl="ref"):
    ring = ring_context()
    if (ring is not None and kv_len is None and isinstance(q_offset, int)
            and q_offset == 0 and q.shape[1] == k.shape[1]
            and q.shape[1] % (2 * ring.cp) == 0):
        # context parallelism: seq sharded through attention, k/v blocks
        # ring-rotate over the cp axis.  Recompute ring blocks in the backward
        # (flash VJP memory semantics) instead of saving per-step probability
        # blocks into the layer-scan residuals.
        from repro.parallel.context import ring_attention

        fn = jax.checkpoint(
            lambda q_, k_, v_: ring_attention(q_, k_, v_, causal=causal,
                                              mesh=ring.mesh, axis=ring.axis),
            policy=jax.checkpoint_policies.nothing_saveable)
        return fn(q, k, v)
    if impl == "flash" and q.shape[1] >= 128:
        from repro.kernels.flash_attention.ops import flash_attention

        return flash_attention(q, k, v, causal=causal)
    if max(q.shape[1], k.shape[1]) <= DENSE_MAX_SEQ:
        return dense_attention(q, k, v, causal=causal, q_offset=q_offset, kv_len=kv_len)
    # Recompute block probabilities in the backward pass — matches the flash
    # kernel's VJP memory semantics (saving them stacks full S² scores into
    # the layer-scan residuals: +17 GB/device at llama train_4k, measured).
    fn = jax.checkpoint(
        lambda q_, k_, v_: chunked_attention(q_, k_, v_, causal=causal,
                                             q_offset=q_offset, kv_len=kv_len),
        policy=jax.checkpoint_policies.nothing_saveable)
    return fn(q, k, v)


# --------------------------------------------------------------------------
# block-level entry point
# --------------------------------------------------------------------------

def _project_qkv(params, x, kv_x, cfg: ModelConfig):
    q = jnp.einsum("bsd,dhk->bshk", x, params["wq"].astype(x.dtype))
    k = jnp.einsum("bsd,dhk->bshk", kv_x, params["wk"].astype(x.dtype))
    v = jnp.einsum("bsd,dhk->bshk", kv_x, params["wv"].astype(x.dtype))
    if "bq" in params:
        q = q + params["bq"].astype(x.dtype)
        k = k + params["bk"].astype(x.dtype)
        v = v + params["bv"].astype(x.dtype)
    if "q_norm" in params:
        q = head_rmsnorm(params["q_norm"], q, cfg.norm_eps)
        k = head_rmsnorm(params["k_norm"], k, cfg.norm_eps)
    return q, k, v


def _out_proj(params, out, x_dtype, num_heads: int):
    """out: (B,S,Hp,hd) possibly padded; wo rows beyond num_heads are zero."""
    wo = params["wo"].astype(x_dtype)
    Hp = out.shape[2]
    if Hp != wo.shape[0]:
        wo = jnp.pad(wo, ((0, Hp - wo.shape[0]), (0, 0), (0, 0)))
    return jnp.einsum("bshk,hkd->bsd", out, wo)


@scoped("attention")
def attention_block(
    params: dict,
    x: jnp.ndarray,                 # (B, Sq, D)
    *,
    cfg: ModelConfig,
    mode: str,                      # "train" | "prefill" | "decode" | "encoder"
    cache: Optional[dict] = None,   # {"k","v": (B, S_max, KV, hd)}
    cache_index=None,               # scalar write offset for decode
    kv_len: Optional[jnp.ndarray] = None,
    kv_source: Optional[jnp.ndarray] = None,  # encoder output for cross-attn
    cross: bool = False,
    impl: str = "ref",
) -> tuple[jnp.ndarray, Optional[dict]]:
    B, Sq, D = x.shape
    cross = cross or kv_source is not None
    kv_x = kv_source if cross else x
    new_cache = None

    if mode == "decode" and cross:
        # cross-attn k/v precomputed at prefill and stored in cache
        q = jnp.einsum("bsd,dhk->bshk", x, params["wq"].astype(x.dtype))
        if "q_norm" in params:
            q = head_rmsnorm(params["q_norm"], q, cfg.norm_eps)
        q, k, v = expand_and_pad(q, cache["k"].astype(q.dtype), cache["v"].astype(q.dtype))
        q = lc(q, "batch", None, "q_heads", None)
        out = attention_math(q, k, v, causal=False, kv_len=kv_len, impl=impl)
        new_cache = cache
    else:
        q, k, v = _project_qkv(params, x, kv_x, cfg)
        if not cross:  # rope only on self-attention
            pos_q = (cache_index + jnp.arange(Sq)) if mode == "decode" else jnp.arange(Sq)
            cos_q, sin_q = rope_angles(pos_q, cfg.resolved_head_dim, cfg.rope_theta)
            q = apply_rope(q, cos_q, sin_q)
            k = apply_rope(k, cos_q, sin_q)

        if mode == "decode":
            ck = jax.lax.dynamic_update_slice_in_dim(
                cache["k"], k.astype(cache["k"].dtype), cache_index, axis=1)
            cv = jax.lax.dynamic_update_slice_in_dim(
                cache["v"], v.astype(cache["v"].dtype), cache_index, axis=1)
            new_cache = {"k": ck, "v": cv}
            valid = kv_len if kv_len is not None else jnp.full((B,), 1, jnp.int32) * (cache_index + Sq)
            q, ke, ve = expand_and_pad(q, ck.astype(q.dtype), cv.astype(q.dtype))
            q = lc(q, "batch", None, "q_heads", None)
            out = attention_math(q, ke, ve, causal=True, q_offset=cache_index,
                                 kv_len=valid, impl=impl)
        else:
            causal = mode != "encoder" and not cross
            if mode == "prefill":
                new_cache = {"k": k, "v": v}
            q, ke, ve = expand_and_pad(q, k, v)
            # "cp_seq" keeps the seq dim context-parallel-sharded inside the
            # TP region (no-op without an active cp axis)
            q = lc(q, "batch", "cp_seq", "q_heads", None)
            ke = lc(ke, "batch", "cp_seq", "q_heads", None)
            ve = lc(ve, "batch", "cp_seq", "q_heads", None)
            out = attention_math(q, ke, ve, causal=causal, kv_len=kv_len, impl=impl)

    out = lc(out, "batch", "cp_seq", "q_heads", None)
    y = _out_proj(params, out, x.dtype, cfg.num_heads)
    return lc(y, "batch", "seq", "embed"), new_cache


def init_kv_cache(cfg: ModelConfig, batch: int, max_len: int, layers: int, dtype=jnp.bfloat16):
    kv, hd = cfg.num_kv_heads, cfg.resolved_head_dim
    shape = (layers, batch, max_len, kv, hd)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def abstract_kv_cache(cfg: ModelConfig, batch: int, max_len: int, layers: int, dtype=jnp.bfloat16):
    kv, hd = cfg.num_kv_heads, cfg.resolved_head_dim
    shape = (layers, batch, max_len, kv, hd)
    return {"k": jax.ShapeDtypeStruct(shape, dtype), "v": jax.ShapeDtypeStruct(shape, dtype)}
