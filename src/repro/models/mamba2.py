"""Mamba2 (SSD) blocks — attention-free LM, O(1)-state decode.

Block: RMSNorm -> {z, x, B, C, dt} projections -> causal depthwise conv on
(x|B|C) -> SSD scan -> D-skip -> gated RMSNorm(y * silu(z)) -> out-proj.
Projections are kept as separate matrices (not one fused in_proj) so each
carries its own logical axes for tensor parallelism (``ssm_inner`` /
``ssm_heads`` shard over the model axis; ``ssm_state`` never shards).

Decode state per layer: conv ring buffer (W-1 last inputs of the conv
channels) + SSD state (B, H, N, P) — constant in context length, which is why
mamba2/zamba2 are the two archs that run the ``long_500k`` cell.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.registry import ModelConfig
from repro.models import embedding
from repro.models.common import (ParamDef, abstract_params, init_params, scan_or_unroll,
                                 scoped, stacked)
from repro.models.norms import gated_rmsnorm, rmsnorm, rmsnorm_defs
from repro.models.transformer import default_layer_runner
from repro.kernels.ssd import ops as ssd_ops
from repro.kernels.ssd.ref import _expand_groups
from repro.parallel.axes import lc


def _dims(cfg: ModelConfig):
    d_inner = cfg.ssm_expand * cfg.d_model
    H = d_inner // cfg.ssm_head_dim
    return d_inner, H, cfg.ssm_groups, cfg.ssm_state, cfg.ssm_head_dim


def mamba_block_defs(cfg: ModelConfig) -> dict:
    d = cfg.d_model
    d_inner, H, G, N, P = _dims(cfg)
    W = cfg.conv_width
    return {
        "ln": rmsnorm_defs(d),
        "w_z": ParamDef((d, d_inner), ("embed", "ssm_inner")),
        "w_x": ParamDef((d, d_inner), ("embed", "ssm_inner")),
        "w_B": ParamDef((d, G * N), ("embed", "ssm_groups")),
        "w_C": ParamDef((d, G * N), ("embed", "ssm_groups")),
        "w_dt": ParamDef((d, H), ("embed", "ssm_heads")),
        "conv_x": ParamDef((W, d_inner), ("conv", "ssm_inner"), scale=0.5),
        "conv_B": ParamDef((W, G * N), ("conv", "ssm_groups"), scale=0.5),
        "conv_C": ParamDef((W, G * N), ("conv", "ssm_groups"), scale=0.5),
        "A_log": ParamDef((H,), ("ssm_heads",), init="zeros"),
        "D": ParamDef((H,), ("ssm_heads",), init="ones"),
        "dt_bias": ParamDef((H,), ("ssm_heads",), init="zeros"),
        "gate_norm": rmsnorm_defs(d_inner),
        "w_out": ParamDef((d_inner, d), ("ssm_inner", "embed")),
    }


def _causal_conv(x: jnp.ndarray, w: jnp.ndarray) -> jnp.ndarray:
    """Depthwise causal conv. x: (B, S, C); w: (W, C)."""
    W = w.shape[0]
    pad = jnp.pad(x, ((0, 0), (W - 1, 0), (0, 0)))
    out = jnp.zeros_like(x)
    for k in range(W):  # W=4: unrolled shifted adds beat lax.conv on TPU here
        out = out + pad[:, k:k + x.shape[1], :] * w[W - 1 - k][None, None, :]
    return out


def _conv_step(buf: jnp.ndarray, x_t: jnp.ndarray, w: jnp.ndarray):
    """buf: (B, W-1, C) past inputs; x_t: (B, C). Returns (new_buf, y_t).

    Tap order must mirror ``_causal_conv``: w[0] multiplies the NEWEST
    sample, w[W-1] the oldest — the window is oldest->newest, so flip w."""
    window = jnp.concatenate([buf, x_t[:, None, :]], axis=1)        # (B, W, C)
    y = jnp.einsum("bwc,wc->bc", window, w[::-1])
    return window[:, 1:, :], y


@scoped("ssm_proj")
def _projections(params, h, cfg):
    dtype = h.dtype
    z = jnp.einsum("bsd,di->bsi", h, params["w_z"].astype(dtype))
    xv = jnp.einsum("bsd,di->bsi", h, params["w_x"].astype(dtype))
    Bv = jnp.einsum("bsd,dg->bsg", h, params["w_B"].astype(dtype))
    Cv = jnp.einsum("bsd,dg->bsg", h, params["w_C"].astype(dtype))
    dt_raw = jnp.einsum("bsd,dh->bsh", h, params["w_dt"].astype(dtype))
    z = lc(z, "batch", None, "ssm_inner")
    xv = lc(xv, "batch", None, "ssm_inner")
    return z, xv, Bv, Cv, dt_raw


def mamba_block_apply(
    params: dict,
    x: jnp.ndarray,                      # (B, S, D)
    cfg: ModelConfig,
    *,
    mode: str = "train",
    state: Optional[dict] = None,        # decode: {"conv_x","conv_B","conv_C","ssm"}
    impl: str = "ref",
):
    """Named scopes: ``norm`` (both norms), ``ssm_proj`` (the five
    in-projections and the out-projection), ``ssm_conv`` (the causal convs
    and their SiLU), ``ssd`` (discretization, the scan and the D skip)."""
    d_inner, H, G, N, P = _dims(cfg)
    Bsz, S, _ = x.shape
    h = rmsnorm(params["ln"], x, cfg.norm_eps)
    z, xv, Bv, Cv, dt_raw = _projections(params, h, cfg)

    with jax.named_scope("ssd"):
        A = -jnp.exp(params["A_log"].astype(jnp.float32))
        dt = jax.nn.softplus(dt_raw.astype(jnp.float32)
                             + params["dt_bias"].astype(jnp.float32))

    new_state = None
    if mode == "decode":
        with jax.named_scope("ssm_conv"):
            cbx, ox = _conv_step(state["conv_x"], xv[:, 0], params["conv_x"].astype(xv.dtype))
            cbB, oB = _conv_step(state["conv_B"], Bv[:, 0], params["conv_B"].astype(xv.dtype))
            cbC, oC = _conv_step(state["conv_C"], Cv[:, 0], params["conv_C"].astype(xv.dtype))
            ox, oB, oC = jax.nn.silu(ox), jax.nn.silu(oB), jax.nn.silu(oC)
        with jax.named_scope("ssd"):
            xh = ox.reshape(Bsz, H, P).astype(jnp.float32)
            Bt = _expand_groups(oB.reshape(Bsz, 1, G, N), H)[:, 0].astype(jnp.float32)
            Ct = _expand_groups(oC.reshape(Bsz, 1, G, N), H)[:, 0].astype(jnp.float32)
            ssm, y_t = ssd_ops.ssd_step(state["ssm"], xh, dt[:, 0], A, Bt, Ct)
            y = y_t[:, None].astype(x.dtype)                        # (B,1,H,P)
            y = y + params["D"].astype(x.dtype)[None, None, :, None] * xh[:, None].astype(x.dtype)
        new_state = {"conv_x": cbx, "conv_B": cbB, "conv_C": cbC, "ssm": ssm}
    else:
        with jax.named_scope("ssm_conv"):
            ox = jax.nn.silu(_causal_conv(xv, params["conv_x"].astype(xv.dtype)))
            oB = jax.nn.silu(_causal_conv(Bv, params["conv_B"].astype(xv.dtype)))
            oC = jax.nn.silu(_causal_conv(Cv, params["conv_C"].astype(xv.dtype)))
        with jax.named_scope("ssd"):
            xh = ox.reshape(Bsz, S, H, P)
            Bm = oB.reshape(Bsz, S, G, N)
            Cm = oC.reshape(Bsz, S, G, N)
            xh = lc(xh, "batch", None, "ssm_heads", None)
            y, final = ssd_ops.ssd(xh.astype(jnp.float32), dt, A,
                                   Bm.astype(jnp.float32), Cm.astype(jnp.float32), impl=impl)
            y = y.astype(x.dtype)
            y = y + params["D"].astype(x.dtype)[None, None, :, None] * xh
            y = lc(y, "batch", None, "ssm_heads", None)
        if mode == "prefill":
            W = cfg.conv_width
            new_state = {
                "conv_x": xv[:, S - (W - 1):, :],
                "conv_B": Bv[:, S - (W - 1):, :],
                "conv_C": Cv[:, S - (W - 1):, :],
                "ssm": final,
            }

    y = y.reshape(Bsz, y.shape[1], d_inner)
    y = gated_rmsnorm(params["gate_norm"], y, z[:, : y.shape[1]], cfg.norm_eps)
    with jax.named_scope("ssm_proj"):
        out = jnp.einsum("bsi,id->bsd", y, params["w_out"].astype(x.dtype))
    return lc(x + out, "batch", "seq", "embed"), new_state


class Mamba2LM:
    """Pure-SSM LM (mamba2-2.7b)."""

    def __init__(self, cfg: ModelConfig, impl: str = "ref"):
        self.cfg = cfg
        self.impl = impl

    def block_defs(self) -> dict:
        return mamba_block_defs(self.cfg)

    def block_apply(self, params, x, *, mode="train", cache=None,
                    cache_index=None, kv_len=None):
        """Uniform block interface (used by the pipeline-parallel path)."""
        out, state = mamba_block_apply(params, x, self.cfg, mode=mode,
                                       state=cache, impl=self.impl)
        return out, state, jnp.float32(0.0)

    def param_defs(self) -> dict:
        cfg = self.cfg
        return {
            "embed": embedding.embed_defs(cfg),
            "blocks": stacked(self.block_defs(), cfg.num_layers),
            "final_norm": rmsnorm_defs(cfg.d_model),
        }

    def init(self, key):
        return init_params(self.param_defs(), key)

    def abstract(self):
        return abstract_params(self.param_defs())

    # ------------------------------------------------------------ train
    def forward_train(self, params, tokens, *, vis_embeds=None, layer_runner=None,
                      dtype=jnp.bfloat16, zero_head=None):
        runner = layer_runner or default_layer_runner
        x = embedding.embed_tokens(params["embed"], tokens, dtype)

        def apply_block(bp, h):
            out, _ = mamba_block_apply(bp, h, self.cfg, mode="train", impl=self.impl)
            return out, jnp.float32(0.0)

        x, extra = runner(params["blocks"], x, apply_block)
        x = rmsnorm(params["final_norm"], x, self.cfg.norm_eps)
        return embedding.lm_head(params["embed"], x, self.cfg, zero_head), extra

    # ------------------------------------------------------------ serving
    def _state_shapes(self, batch: int):
        cfg = self.cfg
        d_inner, H, G, N, P = _dims(cfg)
        W = cfg.conv_width
        L = cfg.num_layers
        return {
            "conv_x": ((L, batch, W - 1, d_inner), jnp.bfloat16),
            "conv_B": ((L, batch, W - 1, G * N), jnp.bfloat16),
            "conv_C": ((L, batch, W - 1, G * N), jnp.bfloat16),
            "ssm": ((L, batch, H, N, P), jnp.float32),
        }

    def init_cache(self, batch: int, max_len: int, dtype=jnp.bfloat16):
        return {k: jnp.zeros(s, d) for k, (s, d) in self._state_shapes(batch).items()}

    def abstract_cache(self, batch: int, max_len: int, dtype=jnp.bfloat16):
        return {k: jax.ShapeDtypeStruct(s, d) for k, (s, d) in self._state_shapes(batch).items()}

    def cache_logical_axes(self):
        return {
            "conv_x": ("layers", "batch", None, "ssm_inner"),
            "conv_B": ("layers", "batch", None, "ssm_groups"),
            "conv_C": ("layers", "batch", None, "ssm_groups"),
            "ssm": ("layers", "batch", "ssm_heads", None, None),
        }

    def forward_prefill(self, params, tokens, *, max_len=None, vis_embeds=None,
                        dtype=jnp.bfloat16, unroll: bool = False):
        x = embedding.embed_tokens(params["embed"], tokens, dtype)

        def body(carry, layer_params):
            out, st = mamba_block_apply(layer_params, carry, self.cfg,
                                        mode="prefill", impl=self.impl)
            return out, st

        x, cache = scan_or_unroll(body, x, params["blocks"], unroll=unroll)
        x = rmsnorm(params["final_norm"], x, self.cfg.norm_eps)
        logits = embedding.lm_head(params["embed"], x[:, -1:, :], self.cfg)
        return logits, cache

    def forward_decode(self, params, tokens, cache, cache_index, *, kv_len=None,
                       dtype=jnp.bfloat16, unroll: bool = False):
        x = embedding.embed_tokens(params["embed"], tokens, dtype)

        def body(carry, xs):
            layer_params, layer_state = xs
            out, st = mamba_block_apply(layer_params, carry, self.cfg,
                                        mode="decode", state=layer_state, impl=self.impl)
            return out, st

        x, new_cache = scan_or_unroll(body, x, (params["blocks"], cache), unroll=unroll)
        x = rmsnorm(params["final_norm"], x, self.cfg.norm_eps)
        logits = embedding.lm_head(params["embed"], x, self.cfg)
        return logits, new_cache

    def text_offset(self) -> int:
        return 0
