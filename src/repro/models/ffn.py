"""Feed-forward variants: SwiGLU (llama/qwen), squared-ReLU (nemotron), GELU."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.registry import ModelConfig
from repro.models.common import ParamDef, scoped
from repro.parallel.axes import lc


def ffn_defs(cfg: ModelConfig, d_ff: int | None = None) -> dict:
    d, f = cfg.d_model, d_ff if d_ff is not None else cfg.d_ff
    defs = {
        "w_in": ParamDef((d, f), ("embed", "ff")),
        "w_out": ParamDef((f, d), ("ff", "embed")),
    }
    if cfg.mlp_type in ("swiglu", "geglu"):
        defs["w_gate"] = ParamDef((d, f), ("embed", "ff"))
    return defs


@scoped("mlp")
def ffn_apply(params: dict, x: jnp.ndarray, cfg: ModelConfig) -> jnp.ndarray:
    w_in = params["w_in"].astype(x.dtype)
    h = jnp.einsum("bsd,df->bsf", x, w_in)
    if cfg.mlp_type in ("swiglu", "geglu"):
        g = jnp.einsum("bsd,df->bsf", x, params["w_gate"].astype(x.dtype))
        act = jax.nn.silu if cfg.mlp_type == "swiglu" else jax.nn.gelu
        h = act(g) * h
    elif cfg.mlp_type == "relu2":
        r = jax.nn.relu(h)
        h = r * r
    elif cfg.mlp_type == "gelu":
        h = jax.nn.gelu(h)
    else:
        raise ValueError(f"unknown mlp_type {cfg.mlp_type!r}")
    h = lc(h, "batch", None, "ff")
    y = jnp.einsum("bsf,fd->bsd", h, params["w_out"].astype(x.dtype))
    return lc(y, "batch", "seq", "embed")
