"""Pieces the plain references share: matmuls, RMSNorm, the loss, AdamW.

Nothing here imports the system under test.  Every matrix product goes
through :func:`mm`, which runs either in float32 at ``highest`` precision
(the reference) or with both operands rounded to fp8 (the control: e4m3
operands forward, e5m2 cotangents backward, each tensor scaled to its own
absolute maximum, products accumulated in float32).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST

#: The training recipe both references follow, as stated in the
#: configuration files: AdamW, decoupled weight decay on every stored leaf
#: of two or more dimensions, global-norm clipping of the gradient.
ADAMW = {"lr": 3e-4, "b1": 0.9, "b2": 0.95, "eps": 1e-8,
         "weight_decay": 0.1, "grad_clip": 1.0}

#: The loss: mean next-token cross-entropy plus 1e-4 times the mean squared
#: log-partition (z-loss).
Z_LOSS = 1e-4

_E4M3_MAX = 448.0
_E5M2_MAX = 57344.0


def _round_fp8(x, dtype, fmax):
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / fmax
    return (x / scale).astype(dtype).astype(jnp.float32) * scale


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _mm_fp8(spec, a, b):
    qa = _round_fp8(a, jnp.float8_e4m3fn, _E4M3_MAX)
    qb = _round_fp8(b, jnp.float8_e4m3fn, _E4M3_MAX)
    return jnp.einsum(spec, qa, qb, precision=HIGHEST)


def _mm_fp8_fwd(spec, a, b):
    qa = _round_fp8(a, jnp.float8_e4m3fn, _E4M3_MAX)
    qb = _round_fp8(b, jnp.float8_e4m3fn, _E4M3_MAX)
    return jnp.einsum(spec, qa, qb, precision=HIGHEST), (qa, qb)


def _mm_fp8_bwd(spec, res, ct):
    qa, qb = res
    qct = _round_fp8(ct, jnp.float8_e5m2, _E5M2_MAX)
    _, vjp = jax.vjp(lambda x, y: jnp.einsum(spec, x, y, precision=HIGHEST),
                     qa, qb)
    return vjp(qct)


_mm_fp8.defvjp(_mm_fp8_fwd, _mm_fp8_bwd)


def mm(spec: str, a, b, mode: str = "f32"):
    """``einsum(spec, a, b)`` in the reference's precision or the control's."""
    if mode == "f32":
        return jnp.einsum(spec, a, b, precision=HIGHEST)
    if mode == "fp8":
        return _mm_fp8(spec, a, b)
    raise ValueError(f"unknown matmul mode {mode!r}")


def rmsnorm(x, scale, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale


def xent_blocks(h, w_head, labels, mode: str, block: int):
    """Sum over tokens of (nll, lse^2) for final hidden states ``h``
    (B, S, D) against ``w_head`` (D, V), a block of ``block`` positions at a
    time so that the (B, block, V) logits are all that is ever live."""
    B, S, D = h.shape
    nb = S // block
    hb = jnp.moveaxis(h.reshape(B, nb, block, D), 1, 0)
    lb = jnp.moveaxis(labels.reshape(B, nb, block), 1, 0)

    @jax.checkpoint
    def one(args):
        hx, lx = args
        logits = mm("bsd,dv->bsv", hx, w_head, mode)
        lse = jax.nn.logsumexp(logits, axis=-1)
        picked = jnp.take_along_axis(logits, lx[..., None], axis=-1)[..., 0]
        return jnp.sum(lse - picked), jnp.sum(lse * lse)

    nll, z2 = jax.lax.map(one, (hb, lb))
    return jnp.sum(nll), jnp.sum(z2)


def loss_from_sums(nll_sum, z2_sum, n_tokens):
    return nll_sum / n_tokens + Z_LOSS * z2_sum / n_tokens


def adamw_step(params, grads, m, v, step: int):
    """One AdamW step (``step`` counts from 1).  Returns (params, m, v,
    global gradient norm before clipping)."""
    c = ADAMW
    leaves = jax.tree.leaves(grads)
    gnorm = jnp.sqrt(sum(jnp.sum(g * g) for g in leaves))
    scale = jnp.minimum(1.0, c["grad_clip"] / (gnorm + 1e-12))
    bc1 = 1.0 - c["b1"] ** step
    bc2 = 1.0 - c["b2"] ** step

    def upd(p, g, m_, v_):
        g = g * scale
        m_ = c["b1"] * m_ + (1.0 - c["b1"]) * g
        v_ = c["b2"] * v_ + (1.0 - c["b2"]) * g * g
        u = (m_ / bc1) / (jnp.sqrt(v_ / bc2) + c["eps"])
        if p.ndim >= 2:
            u = u + c["weight_decay"] * p
        return p - c["lr"] * u, m_, v_

    out = jax.tree.map(upd, params, grads, m, v)
    is3 = lambda t: isinstance(t, tuple)
    return (jax.tree.map(lambda t: t[0], out, is_leaf=is3),
            jax.tree.map(lambda t: t[1], out, is_leaf=is3),
            jax.tree.map(lambda t: t[2], out, is_leaf=is3), gnorm)
