"""Whisper-style encoder-decoder.  The conv/mel frontend is a STUB: inputs
are precomputed frame embeddings (B, enc_frames, d_model) from
``input_specs()``, per the assignment.  Decoder = causal self-attn +
cross-attn + FFN; decode uses a self-attn KV cache plus cross-attn K/V
computed once at prefill.
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.configs.registry import ModelConfig
from repro.models import attention as attn
from repro.models import embedding, ffn
from repro.models.common import abstract_params, init_params, scan_or_unroll, stacked
from repro.models.norms import rmsnorm, rmsnorm_defs
from repro.parallel.axes import lc


class EncDecLM:
    def __init__(self, cfg: ModelConfig, impl: str = "ref"):
        self.cfg = cfg
        self.impl = impl

    # ------------------------------------------------------------ params
    def enc_block_defs(self) -> dict:
        cfg = self.cfg
        return {
            "ln1": rmsnorm_defs(cfg.d_model),
            "attn": attn.attn_defs(cfg),
            "ln2": rmsnorm_defs(cfg.d_model),
            "mlp": ffn.ffn_defs(cfg),
        }

    def dec_block_defs(self) -> dict:
        cfg = self.cfg
        return {
            "ln1": rmsnorm_defs(cfg.d_model),
            "self_attn": attn.attn_defs(cfg),
            "ln_x": rmsnorm_defs(cfg.d_model),
            "cross_attn": attn.attn_defs(cfg, cross=True),
            "ln2": rmsnorm_defs(cfg.d_model),
            "mlp": ffn.ffn_defs(cfg),
        }

    def param_defs(self) -> dict:
        cfg = self.cfg
        return {
            "embed": embedding.embed_defs(cfg),
            "enc_blocks": stacked(self.enc_block_defs(), cfg.enc_layers),
            "enc_norm": rmsnorm_defs(cfg.d_model),
            "dec_blocks": stacked(self.dec_block_defs(), cfg.num_layers),
            "final_norm": rmsnorm_defs(cfg.d_model),
        }

    def init(self, key):
        return init_params(self.param_defs(), key)

    def abstract(self):
        return abstract_params(self.param_defs())

    # ------------------------------------------------------------ encoder
    def encode(self, params, frames: jnp.ndarray, unroll: bool = False) -> jnp.ndarray:
        """frames: (B, F, D) stub embeddings -> encoder output (B, F, D)."""
        cfg = self.cfg
        x = lc(frames, "batch", "seq", "embed")

        def body(carry, lp):
            h = rmsnorm(lp["ln1"], carry, cfg.norm_eps)
            a, _ = attn.attention_block(lp["attn"], h, cfg=cfg, mode="encoder",
                                        impl=self.impl)
            carry = carry + a
            h = rmsnorm(lp["ln2"], carry, cfg.norm_eps)
            carry = lc(carry + ffn.ffn_apply(lp["mlp"], h, cfg), "batch", "seq", "embed")
            return carry, None

        x, _ = scan_or_unroll(body, x, params["enc_blocks"], unroll=unroll)
        return rmsnorm(params["enc_norm"], x, cfg.norm_eps)

    # ------------------------------------------------------------ decoder block
    def _dec_block(self, lp, x, enc_out, *, mode, self_cache=None, cross_cache=None,
                   cache_index=None, kv_len=None):
        cfg = self.cfg
        h = rmsnorm(lp["ln1"], x, cfg.norm_eps)
        a, new_self = attn.attention_block(
            lp["self_attn"], h, cfg=cfg, mode=mode, cache=self_cache,
            cache_index=cache_index, kv_len=kv_len, impl=self.impl)
        x = x + a
        h = rmsnorm(lp["ln_x"], x, cfg.norm_eps)
        a, new_cross = attn.attention_block(
            lp["cross_attn"], h, cfg=cfg, mode=mode,
            cache=cross_cache, kv_source=enc_out, cross=True, impl=self.impl)
        x = x + a
        h = rmsnorm(lp["ln2"], x, cfg.norm_eps)
        x = lc(x + ffn.ffn_apply(lp["mlp"], h, cfg), "batch", "seq", "embed")
        return x, new_self, new_cross

    # ------------------------------------------------------------ train
    def forward_train(self, params, tokens, *, frames=None, vis_embeds=None,
                      layer_runner=None, dtype=jnp.bfloat16, unroll: bool = False,
                      zero_head=None):
        """tokens: (B, S) decoder input; frames: (B, F, D) stub encoder input."""
        cfg = self.cfg
        if frames is None:  # smoke-test convenience: derive stub frames from zeros
            frames = jnp.zeros((tokens.shape[0], cfg.enc_frames, cfg.d_model), dtype)
        enc_out = self.encode(params, frames.astype(dtype), unroll=unroll)
        x = embedding.embed_tokens(params["embed"], tokens, dtype)

        def body(carry, lp):
            out, _, _ = self._dec_block(lp, carry, enc_out, mode="train")
            return out, None

        x, _ = scan_or_unroll(body, x, params["dec_blocks"], unroll=unroll)
        x = rmsnorm(params["final_norm"], x, cfg.norm_eps)
        return embedding.lm_head(params["embed"], x, cfg, zero_head), jnp.float32(0.0)

    # ------------------------------------------------------------ serving
    def init_cache(self, batch, max_len, dtype=jnp.bfloat16):
        cfg = self.cfg
        return {
            "self": attn.init_kv_cache(cfg, batch, max_len, cfg.num_layers, dtype),
            "cross": attn.init_kv_cache(cfg, batch, cfg.enc_frames, cfg.num_layers, dtype),
        }

    def abstract_cache(self, batch, max_len, dtype=jnp.bfloat16):
        cfg = self.cfg
        return {
            "self": attn.abstract_kv_cache(cfg, batch, max_len, cfg.num_layers, dtype),
            "cross": attn.abstract_kv_cache(cfg, batch, cfg.enc_frames, cfg.num_layers, dtype),
        }

    def cache_logical_axes(self):
        kv = {"k": ("layers", "batch", "seq", "kv_heads", None),
              "v": ("layers", "batch", "seq", "kv_heads", None)}
        return {"self": kv, "cross": dict(kv)}

    def forward_prefill(self, params, tokens, *, frames=None, max_len=None,
                        vis_embeds=None, dtype=jnp.bfloat16, unroll: bool = False):
        cfg = self.cfg
        B, S = tokens.shape
        max_len = max_len or S
        if frames is None:
            frames = jnp.zeros((B, cfg.enc_frames, cfg.d_model), dtype)
        enc_out = self.encode(params, frames.astype(dtype), unroll=unroll)
        x = embedding.embed_tokens(params["embed"], tokens, dtype)

        def body(carry, lp):
            out, new_self, new_cross = self._dec_block(lp, carry, enc_out, mode="prefill")
            pad = max_len - S
            new_self = {k: jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
                        for k, v in new_self.items()}
            return out, (new_self, new_cross)

        x, (self_cache, cross_cache) = scan_or_unroll(body, x, params["dec_blocks"], unroll=unroll)
        x = rmsnorm(params["final_norm"], x, cfg.norm_eps)
        logits = embedding.lm_head(params["embed"], x[:, -1:, :], cfg)
        return logits, {"self": self_cache, "cross": cross_cache}

    def forward_decode(self, params, tokens, cache, cache_index, *, kv_len=None,
                       dtype=jnp.bfloat16, unroll: bool = False):
        cfg = self.cfg
        x = embedding.embed_tokens(params["embed"], tokens, dtype)

        def body(carry, xs):
            lp, self_c, cross_c = xs
            out, new_self, new_cross = self._dec_block(
                lp, carry, None, mode="decode", self_cache=self_c, cross_cache=cross_c,
                cache_index=cache_index, kv_len=kv_len)
            return out, (new_self, new_cross)

        x, (new_self, new_cross) = scan_or_unroll(
            body, x, (params["dec_blocks"], cache["self"], cache["cross"]), unroll=unroll)
        x = rmsnorm(params["final_norm"], x, cfg.norm_eps)
        logits = embedding.lm_head(params["embed"], x, cfg)
        return logits, {"self": new_self, "cross": new_cross}

    def text_offset(self) -> int:
        return 0
