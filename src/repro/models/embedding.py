"""Token embedding and LM head (optionally tied)."""
from __future__ import annotations

from typing import Optional

import jax.numpy as jnp

from repro import obs
from repro.configs.registry import ModelConfig
from repro.models.common import ParamDef, scoped
from repro.parallel import zero_head as zh_lib
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
def lm_head(params: dict, x: jnp.ndarray, cfg: ModelConfig,
            zero_head: Optional[zh_lib.ZeroHead] = None) -> jnp.ndarray:
    """Returns fp32 logits (B, S, V).  Given the weight's ZeRO-3 layout
    (``zero_head``), its gather and its gradient's reduce-scatter run in
    chunks under the head's matmuls (``parallel/zero_head.py``).  Records a
    span naming the path built: ``head_zero_overlap/<chunks>`` or
    ``head_plain``."""
    w = params["tok"] if cfg.tie_embeddings else params["head"]
    vocab_dim = 0 if cfg.tie_embeddings else 1
    k = zh_lib.chunks_for(zero_head, x.shape, w.shape[vocab_dim])
    with obs.span(f"head_zero_overlap/{k}" if k else "head_plain"):
        if k:
            logits = zh_lib.gathered_logits(x, w.astype(x.dtype), zero_head,
                                            k, vocab_dim)
        else:
            w = w.astype(x.dtype).T if cfg.tie_embeddings else w.astype(x.dtype)
            logits = jnp.einsum("bsd,dv->bsv", x, w).astype(jnp.float32)
    return lc(logits, "batch", None, "vocab")
