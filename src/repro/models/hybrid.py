"""Zamba2-style hybrid: Mamba2 backbone + one weight-SHARED attention block
applied after every ``attn_every`` mamba layers.

81 layers, attn_every=6 -> 13 applications of the shared block (+3 trailing
mamba layers).  The shared block's parameters are stored once; the memory
model in repro.core counts them once while the time model counts every
application — exactly the distinction Galvatron's per-layer cost model needs.

Decode state = per-layer mamba states + one KV cache per shared-block
*application site* (weights shared, caches not).  SSD state is O(1) in
context and attention at decode is O(S) per token, so this arch runs the
``long_500k`` cell.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.registry import ModelConfig
from repro.models import attention as attn
from repro.models import embedding, ffn
from repro.models.common import scan_or_unroll, stacked
from repro.models.mamba2 import Mamba2LM, mamba_block_apply, mamba_block_defs
from repro.models.norms import rmsnorm, rmsnorm_defs
from repro.parallel.axes import lc


class HybridLM(Mamba2LM):
    def __init__(self, cfg: ModelConfig, impl: str = "ref"):
        super().__init__(cfg, impl)
        assert cfg.attn_every > 0
        self.n_apps = cfg.num_layers // cfg.attn_every         # shared-block sites
        self.covered = self.n_apps * cfg.attn_every
        self.remainder = cfg.num_layers - self.covered

    # ------------------------------------------------------------ params
    def shared_block_defs(self) -> dict:
        cfg = self.cfg
        return {
            "ln1": rmsnorm_defs(cfg.d_model),
            "attn": attn.attn_defs(cfg),
            "ln2": rmsnorm_defs(cfg.d_model),
            "mlp": ffn.ffn_defs(cfg),
        }

    def param_defs(self) -> dict:
        cfg = self.cfg
        return {
            "embed": embedding.embed_defs(cfg),
            "blocks": stacked(mamba_block_defs(cfg), cfg.num_layers),
            "shared_attn": self.shared_block_defs(),            # stored ONCE
            "final_norm": rmsnorm_defs(cfg.d_model),
        }

    # ------------------------------------------------------------ shared block
    def _shared_apply(self, params, x, *, mode, cache=None, cache_index=None, kv_len=None):
        cfg = self.cfg
        h = rmsnorm(params["ln1"], x, cfg.norm_eps)
        a, new_cache = attn.attention_block(
            params["attn"], h, cfg=cfg, mode=mode, cache=cache,
            cache_index=cache_index, kv_len=kv_len, impl=self.impl)
        x = lc(x + a, "batch", "seq", "embed")
        h = rmsnorm(params["ln2"], x, cfg.norm_eps)
        x = lc(x + ffn.ffn_apply(params["mlp"], h, cfg), "batch", "seq", "embed")
        return x, new_cache

    def _split_stacks(self, blocks):
        seg = jax.tree.map(lambda a: a[: self.covered].reshape(
            (self.n_apps, self.cfg.attn_every) + a.shape[1:]), blocks)
        tail = jax.tree.map(lambda a: a[self.covered:], blocks)
        return seg, tail

    # ------------------------------------------------------------ train
    def forward_train(self, params, tokens, *, vis_embeds=None, layer_runner=None,
                      dtype=jnp.bfloat16, unroll: bool = False, zero_head=None):
        cfg = self.cfg
        x = embedding.embed_tokens(params["embed"], tokens, dtype)
        seg_params, tail_params = self._split_stacks(params["blocks"])

        def mamba_scan(h, stacked_params):
            def body(c, lp):
                out, _ = mamba_block_apply(lp, c, cfg, mode="train", impl=self.impl)
                return out, None
            h, _ = scan_or_unroll(body, h, stacked_params, unroll=unroll)
            return h

        def segment(h, seg_lp):
            h = mamba_scan(h, seg_lp)
            h, _ = self._shared_apply(params["shared_attn"], h, mode="train")
            return h, None

        x, _ = scan_or_unroll(segment, x, seg_params, unroll=unroll)
        if self.remainder:
            x = mamba_scan(x, tail_params)
        x = rmsnorm(params["final_norm"], x, cfg.norm_eps)
        return embedding.lm_head(params["embed"], x, cfg, zero_head), jnp.float32(0.0)

    # ------------------------------------------------------------ serving
    def init_cache(self, batch: int, max_len: int, dtype=jnp.bfloat16):
        mamba = super().init_cache(batch, max_len, dtype)
        kv = attn.init_kv_cache(self.cfg, batch, max_len, self.n_apps, dtype)
        return {"mamba": mamba, "attn": kv}

    def abstract_cache(self, batch: int, max_len: int, dtype=jnp.bfloat16):
        mamba = super().abstract_cache(batch, max_len, dtype)
        kv = attn.abstract_kv_cache(self.cfg, batch, max_len, self.n_apps, dtype)
        return {"mamba": mamba, "attn": kv}

    def cache_logical_axes(self):
        return {
            "mamba": super().cache_logical_axes(),
            "attn": {"k": ("layers", "batch", "seq", "kv_heads", None),
                     "v": ("layers", "batch", "seq", "kv_heads", None)},
        }

    def forward_prefill(self, params, tokens, *, max_len=None, vis_embeds=None,
                        dtype=jnp.bfloat16, unroll: bool = False):
        cfg = self.cfg
        x = embedding.embed_tokens(params["embed"], tokens, dtype)
        B, S = tokens.shape
        max_len = max_len or S
        seg_params, tail_params = self._split_stacks(params["blocks"])

        def mamba_scan_collect(h, stacked_params):
            def body(c, lp):
                out, st = mamba_block_apply(lp, c, cfg, mode="prefill", impl=self.impl)
                return out, st
            return scan_or_unroll(body, h, stacked_params, unroll=unroll)

        def segment(h, seg_lp):
            h, states = mamba_scan_collect(h, seg_lp)
            h, kv = self._shared_apply(params["shared_attn"], h, mode="prefill")
            pad = max_len - S
            kv = {k: jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0))) for k, v in kv.items()}
            return h, (states, kv)

        x, (seg_states, kv_cache) = scan_or_unroll(segment, x, seg_params, unroll=unroll)
        # seg_states leaves: (n_apps, attn_every, B, ...) -> flatten to (covered, B, ...)
        mamba_states = jax.tree.map(
            lambda a: a.reshape((self.covered,) + a.shape[2:]), seg_states)
        if self.remainder:
            x, tail_states = mamba_scan_collect(x, tail_params)
            mamba_states = jax.tree.map(
                lambda a, b: jnp.concatenate([a, b], axis=0), mamba_states, tail_states)
        x = rmsnorm(params["final_norm"], x, cfg.norm_eps)
        logits = embedding.lm_head(params["embed"], x[:, -1:, :], cfg)
        return logits, {"mamba": mamba_states, "attn": kv_cache}

    def forward_decode(self, params, tokens, cache, cache_index, *, kv_len=None,
                       dtype=jnp.bfloat16, unroll: bool = False):
        cfg = self.cfg
        x = embedding.embed_tokens(params["embed"], tokens, dtype)
        seg_params, tail_params = self._split_stacks(params["blocks"])
        seg_states = jax.tree.map(lambda a: a[: self.covered].reshape(
            (self.n_apps, cfg.attn_every) + a.shape[1:]), cache["mamba"])
        tail_states = jax.tree.map(lambda a: a[self.covered:], cache["mamba"])

        def mamba_step_scan(h, lp_st):
            def body(c, xs):
                lp, st = xs
                out, new_st = mamba_block_apply(lp, c, cfg, mode="decode",
                                                state=st, impl=self.impl)
                return out, new_st
            return scan_or_unroll(body, h, lp_st, unroll=unroll)

        def segment(h, xs):
            seg_lp, seg_st, kv = xs
            h, new_st = mamba_step_scan(h, (seg_lp, seg_st))
            h, new_kv = self._shared_apply(params["shared_attn"], h, mode="decode",
                                           cache=kv, cache_index=cache_index, kv_len=kv_len)
            return h, (new_st, new_kv)

        x, (new_seg_states, new_kv) = scan_or_unroll(
            segment, x, (seg_params, seg_states, cache["attn"]), unroll=unroll)
        new_mamba = jax.tree.map(
            lambda a: a.reshape((self.covered,) + a.shape[2:]), new_seg_states)
        if self.remainder:
            x, new_tail = mamba_step_scan(x, (tail_params, tail_states))
            new_mamba = jax.tree.map(
                lambda a, b: jnp.concatenate([a, b], axis=0), new_mamba, new_tail)
        x = rmsnorm(params["final_norm"], x, cfg.norm_eps)
        logits = embedding.lm_head(params["embed"], x, cfg)
        return logits, {"mamba": new_mamba, "attn": new_kv}
