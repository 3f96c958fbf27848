"""Decoder-only transformer LM (dense family; base class for MoE and VLM).

Pure-functional: ``param_defs()`` declares parameters (with logical axes),
``forward_train / forward_prefill / forward_decode`` consume the matching
array pytree.  Layers are *stacked* (leading ``layers`` dim) and executed with
``lax.scan`` so the compiled HLO is O(1) in depth; the parallel runtime can
pass a custom ``layer_runner`` that splits the stack into per-strategy groups
(Galvatron's layer-level hybrid parallelism) and applies remat policies.
"""
from __future__ import annotations

from typing import Callable, Optional

import jax
import jax.numpy as jnp

from repro.configs.registry import ModelConfig
from repro.models import attention as attn
from repro.models import embedding, ffn
from repro.models.common import (
    abstract_params,
    init_params,
    scan_or_unroll,
    stacked,
)
from repro.models.norms import rmsnorm, rmsnorm_defs
from repro.parallel.axes import lc
from repro.parallel.zero_head import ZeroHead

# layer_runner(stacked_block_params, x, apply_block) -> x
LayerRunner = Callable


def default_layer_runner(stacked_params, x, apply_block):
    """apply_block(layer_params, h) -> (h, extra); extra (fp32 scalar, e.g.
    MoE aux loss) accumulates through the scan carry."""

    def body(carry, layer_params):
        h, ex = carry
        h2, e2 = apply_block(layer_params, h)
        return (h2, ex + e2), None

    (out, extra), _ = jax.lax.scan(body, (x, jnp.float32(0.0)), stacked_params)
    return out, extra


class DenseTransformerLM:
    supports_layer_grouping = True  # runtime may split the block stack

    def __init__(self, cfg: ModelConfig, impl: str = "ref"):
        self.cfg = cfg
        self.impl = impl

    # ---------------------------------------------------------- params
    def block_defs(self) -> dict:
        cfg = self.cfg
        return {
            "ln1": rmsnorm_defs(cfg.d_model),
            "attn": attn.attn_defs(cfg),
            "ln2": rmsnorm_defs(cfg.d_model),
            "mlp": self.ffn_defs(),
        }

    def ffn_defs(self) -> dict:
        return ffn.ffn_defs(self.cfg)

    def param_defs(self) -> dict:
        cfg = self.cfg
        return {
            "embed": embedding.embed_defs(cfg),
            "blocks": stacked(self.block_defs(), cfg.num_layers),
            "final_norm": rmsnorm_defs(cfg.d_model),
        }

    def init(self, key: jax.Array) -> dict:
        return init_params(self.param_defs(), key)

    def abstract(self) -> dict:
        return abstract_params(self.param_defs())

    # ---------------------------------------------------------- blocks
    def ffn_apply(self, params: dict, x: jnp.ndarray):
        """Returns (y, extra) — extra is a fp32 scalar side loss (0 for dense)."""
        return ffn.ffn_apply(params, x, self.cfg), jnp.float32(0.0)

    def block_apply(
        self,
        params: dict,
        x: jnp.ndarray,
        *,
        mode: str,
        cache: Optional[dict] = None,
        cache_index=None,
        kv_len=None,
    ):
        cfg = self.cfg
        h = rmsnorm(params["ln1"], x, cfg.norm_eps)
        a, new_cache = attn.attention_block(
            params["attn"],
            h,
            cfg=cfg,
            mode=mode,
            cache=cache,
            cache_index=cache_index,
            kv_len=kv_len,
            impl=self.impl,
        )
        x = lc(x + a, "batch", "seq", "embed")
        h = rmsnorm(params["ln2"], x, cfg.norm_eps)
        y, extra = self.ffn_apply(params["mlp"], h)
        x = lc(x + y, "batch", "seq", "embed")
        return x, new_cache, extra

    # ---------------------------------------------------------- forward
    def _embed_inputs(self, params, tokens, vis_embeds=None, dtype=jnp.bfloat16):
        x = embedding.embed_tokens(params["embed"], tokens, dtype)
        if vis_embeds is not None:
            x = jnp.concatenate([vis_embeds.astype(dtype), x], axis=1)
            x = lc(x, "batch", "seq", "embed")
        return x

    def forward_train(
        self,
        params: dict,
        tokens: jnp.ndarray,                    # (B, S) int32
        *,
        vis_embeds: Optional[jnp.ndarray] = None,  # (B, Sv, D) stub frontend
        layer_runner: Optional[LayerRunner] = None,
        dtype=jnp.bfloat16,
        zero_head: Optional[ZeroHead] = None,   # the head weight's ZeRO-3 layout
    ):
        """Returns (logits fp32 (B, S_total, V), extra fp32 scalar)."""
        runner = layer_runner or default_layer_runner
        x = self._embed_inputs(params, tokens, vis_embeds, dtype)

        def apply_block(bp, h):
            out, _, extra = self.block_apply(bp, h, mode="train")
            return out, extra

        x, extra = runner(params["blocks"], x, apply_block)
        x = rmsnorm(params["final_norm"], x, self.cfg.norm_eps)
        return embedding.lm_head(params["embed"], x, self.cfg, zero_head), extra

    # ------------------------------------------------------------ serving
    def init_cache(self, batch: int, max_len: int, dtype=jnp.bfloat16):
        return attn.init_kv_cache(self.cfg, batch, max_len, self.cfg.num_layers, dtype)

    def abstract_cache(self, batch: int, max_len: int, dtype=jnp.bfloat16):
        return attn.abstract_kv_cache(self.cfg, batch, max_len, self.cfg.num_layers, dtype)

    def cache_logical_axes(self):
        return {"k": ("layers", "batch", "seq", "kv_heads", None),
                "v": ("layers", "batch", "seq", "kv_heads", None)}

    def forward_prefill(
        self,
        params: dict,
        tokens: jnp.ndarray,                    # (B, S)
        *,
        max_len: Optional[int] = None,
        vis_embeds: Optional[jnp.ndarray] = None,
        dtype=jnp.bfloat16,
        unroll: bool = False,
    ):
        """Full-sequence pass that also materializes the KV cache (padded to
        ``max_len``).  Returns (last-position logits, cache)."""
        cfg = self.cfg
        x = self._embed_inputs(params, tokens, vis_embeds, dtype)
        B, S = x.shape[0], x.shape[1]
        max_len = max_len or S

        def body(carry, layer_params):
            h = carry
            out, kv, _ = self.block_apply(layer_params, h, mode="prefill")
            pad = max_len - S
            kv = {k: jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0))) for k, v in kv.items()}
            return out, kv

        x, cache = scan_or_unroll(body, x, params["blocks"], unroll=unroll)
        x = rmsnorm(params["final_norm"], x, cfg.norm_eps)
        logits = embedding.lm_head(params["embed"], x[:, -1:, :], cfg)
        return logits, cache

    def forward_decode(
        self,
        params: dict,
        tokens: jnp.ndarray,                    # (B, 1)
        cache: dict,                            # stacked (L, B, S_max, KV, hd)
        cache_index,                            # scalar: write position
        *,
        kv_len: Optional[jnp.ndarray] = None,   # (B,) valid lengths
        dtype=jnp.bfloat16,
        unroll: bool = False,
    ):
        cfg = self.cfg
        x = embedding.embed_tokens(params["embed"], tokens, dtype)

        def body(carry, xs):
            layer_params, layer_cache = xs
            out, new_cache, _ = self.block_apply(
                layer_params, carry, mode="decode",
                cache=layer_cache, cache_index=cache_index, kv_len=kv_len,
            )
            return out, new_cache

        x, new_cache = scan_or_unroll(body, x, (params["blocks"], cache), unroll=unroll)
        x = rmsnorm(params["final_norm"], x, cfg.norm_eps)
        logits = embedding.lm_head(params["embed"], x, cfg)
        return logits, new_cache

    # ------------------------------------------------------------ misc
    def text_offset(self) -> int:
        """Number of non-text prefix positions in train logits (VLM prefix)."""
        return 0


class VLMTransformerLM(DenseTransformerLM):
    """InternVL2-style: LM backbone consuming stub patch embeddings as a prefix."""

    def text_offset(self) -> int:
        return self.cfg.vis_tokens
