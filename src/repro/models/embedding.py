"""Token embedding and LM head (optionally tied)."""
from __future__ import annotations

import jax.numpy as jnp

from repro.configs.registry import ModelConfig
from repro.models.common import ParamDef, scoped
from repro.parallel.axes import lc


def embed_defs(cfg: ModelConfig) -> dict:
    defs = {"tok": ParamDef((cfg.vocab_size, cfg.d_model), ("vocab", "embed"), init="small_normal")}
    if not cfg.tie_embeddings:
        defs["head"] = ParamDef((cfg.d_model, cfg.vocab_size), ("embed", "vocab"))
    return defs


@scoped("embed")
def embed_tokens(params: dict, tokens: jnp.ndarray, dtype=jnp.bfloat16) -> jnp.ndarray:
    x = params["tok"].astype(dtype)[tokens]
    return lc(x, "batch", "seq", "embed")


@scoped("head")
def lm_head(params: dict, x: jnp.ndarray, cfg: ModelConfig) -> jnp.ndarray:
    """Returns fp32 logits (B, S, V)."""
    if cfg.tie_embeddings:
        w = params["tok"].astype(x.dtype).T
    else:
        w = params["head"].astype(x.dtype)
    logits = jnp.einsum("bsd,dv->bsv", x, w).astype(jnp.float32)
    return lc(logits, "batch", None, "vocab")
