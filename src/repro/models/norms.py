"""Normalization layers (fp32 statistics, output in input dtype).

Both norms recompute their fp32 intermediates in the backward pass
(``jax.checkpoint``): without this, every layer's scan residuals stack the
fp32 normalized tensor — measured +2× activation memory at llama train_4k —
for an elementwise op that costs nothing to recompute.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.models.common import ParamDef, scoped


def rmsnorm_defs(dim: int) -> dict:
    return {"scale": ParamDef((dim,), ("norm",), init="ones")}


@functools.partial(jax.checkpoint, policy=jax.checkpoint_policies.nothing_saveable,
                   static_argnums=(2,))
def _rmsnorm(scale: jnp.ndarray, x: jnp.ndarray, eps: float) -> jnp.ndarray:
    dtype = x.dtype
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    y = xf * (var + eps) ** -0.5
    return (y * scale.astype(jnp.float32)).astype(dtype)


@scoped("norm")
def rmsnorm(params: dict, x: jnp.ndarray, eps: float = 1e-5) -> jnp.ndarray:
    return _rmsnorm(params["scale"], x, eps)


@scoped("norm")
def gated_rmsnorm(params: dict, y: jnp.ndarray, z: jnp.ndarray,
                  eps: float = 1e-5) -> jnp.ndarray:
    """Mamba2's gated norm: ``rmsnorm(y * silu(z))``."""
    return _rmsnorm(params["scale"], y * jax.nn.silu(z), eps)


def head_rmsnorm(scale: jnp.ndarray, x: jnp.ndarray, eps: float = 1e-5) -> jnp.ndarray:
    """qk-norm: normalize over the trailing head_dim.  Left out of the
    ``norm`` scope: it runs inside ``attention``, and scopes do not nest."""
    return _rmsnorm(scale, x, eps)
