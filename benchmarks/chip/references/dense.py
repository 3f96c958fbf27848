"""Plain float32 reference of a Qwen3-style dense decoder.

Pre-norm blocks: RMSNorm -> grouped-query attention (q/k RMSNorm over the
head dimension before rotary embeddings, rotate-half convention, causal
softmax at scale head_dim^-0.5) -> residual; RMSNorm -> SwiGLU MLP
(``w_out(silu(w_gate x) * w_in x)``) -> residual; final RMSNorm; untied LM
head.  Keys are the Hugging Face ``config.json`` keys.  Computed in blocks
of query positions and of loss positions so that no (S, S) or (S, V) array
is whole; each layer is recomputed in the backward pass.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from references.common import HIGHEST, mm, rmsnorm, xent_blocks
from weights import Leaf

Q_BLOCK = 512
LOSS_BLOCK = 512
#: the configuration key that counts the layers (the depth cut)
DEPTH_KEY = "num_hidden_layers"


def dims(conf: dict) -> dict:
    return {"D": conf["hidden_size"], "L": conf["num_hidden_layers"],
            "H": conf["num_attention_heads"], "KV": conf["num_key_value_heads"],
            "hd": conf["head_dim"], "F": conf["intermediate_size"],
            "V": conf["vocab_size"], "eps": conf["rms_norm_eps"],
            "theta": conf["rope_theta"], "tied": conf["tie_word_embeddings"]}


def token_vocab(conf: dict) -> int:
    return conf["vocab_size"]


def program_fields(conf: dict) -> dict:
    """The program's ``ModelConfig`` fields this configuration fixes."""
    d = dims(conf)
    return {"family": "dense", "d_model": d["D"], "num_layers": d["L"],
            "num_heads": d["H"], "num_kv_heads": d["KV"], "head_dim": d["hd"],
            "d_ff": d["F"], "vocab_size": d["V"], "norm_eps": d["eps"],
            "rope_theta": d["theta"], "tie_embeddings": d["tied"],
            "qk_norm": True, "qkv_bias": False, "mlp_type": "swiglu"}


def param_spec(conf: dict) -> dict:
    d = dims(conf)
    D, L, H, KV, hd, F, V = (d[k] for k in ("D", "L", "H", "KV", "hd", "F", "V"))
    embed = {"tok": Leaf((V, D), std=0.02)}
    if not d["tied"]:
        embed["head"] = Leaf((D, V), std=D ** -0.5)
    return {
        "embed": embed,
        "blocks": {
            "ln1": {"scale": Leaf((L, D), "ones")},
            "attn": {"wq": Leaf((L, D, H, hd), std=D ** -0.5),
                     "wk": Leaf((L, D, KV, hd), std=D ** -0.5),
                     "wv": Leaf((L, D, KV, hd), std=D ** -0.5),
                     "wo": Leaf((L, H, hd, D), std=(H * hd) ** -0.5),
                     "q_norm": Leaf((L, hd), "ones"),
                     "k_norm": Leaf((L, hd), "ones")},
            "ln2": {"scale": Leaf((L, D), "ones")},
            "mlp": {"w_in": Leaf((L, D, F), std=D ** -0.5),
                    "w_gate": Leaf((L, D, F), std=D ** -0.5),
                    "w_out": Leaf((L, F, D), std=F ** -0.5)},
        },
        "final_norm": {"scale": Leaf((D,), "ones")},
    }


def _rope(x, theta):
    """x (B, S, H, hd) float32, positions 0..S-1, rotate-half."""
    S, hd = x.shape[1], x.shape[-1]
    half = hd // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _attention(q, k, v):
    """Causal GQA; q (B,S,H,hd), k/v (B,S,KV,hd) -> (B,S,H,hd)."""
    B, S, H, hd = q.shape
    KV = k.shape[2]
    g = H // KV
    qb = min(Q_BLOCK, S)
    nb = S // qb
    qs = jnp.moveaxis(q.reshape(B, nb, qb, KV, g, hd), 1, 0)

    @jax.checkpoint
    def block(args):
        i, qi = args
        s = jnp.einsum("bqkgd,bskd->bkgqs", qi, k, precision=HIGHEST)
        s = s * hd ** -0.5
        qpos = i * qb + jnp.arange(qb)
        mask = jnp.arange(S)[None, :] <= qpos[:, None]
        s = jnp.where(mask, s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("bkgqs,bskd->bqkgd", p, v, precision=HIGHEST)

    out = jax.lax.map(block, (jnp.arange(nb), qs))
    return jnp.moveaxis(out, 0, 1).reshape(B, S, H, hd)


def _block(x, lp, d, mode, tp_drop):
    h = rmsnorm(x, lp["ln1"]["scale"], d["eps"])
    a = lp["attn"]
    q = rmsnorm(mm("bsd,dhk->bshk", h, a["wq"], mode), a["q_norm"], d["eps"])
    k = rmsnorm(mm("bsd,dhk->bshk", h, a["wk"], mode), a["k_norm"], d["eps"])
    v = mm("bsd,dhk->bshk", h, a["wv"], mode)
    o = _attention(_rope(q, d["theta"]), _rope(k, d["theta"]), v)
    wo = a["wo"]
    if tp_drop:
        # the row-parallel partial sum of one of two model shards, with the
        # all-reduce that would add the other shard's left out
        keep = d["H"] // 2
        o, wo = o[:, :, :keep], wo[:keep]
    x = x + mm("bshk,hkd->bsd", o, wo, mode)
    h = rmsnorm(x, lp["ln2"]["scale"], d["eps"])
    m = lp["mlp"]
    u = jax.nn.silu(mm("bsd,df->bsf", h, m["w_gate"], mode)) * mm(
        "bsd,df->bsf", h, m["w_in"], mode)
    w_out = m["w_out"]
    if tp_drop:
        keep = d["F"] // 2
        u, w_out = u[..., :keep], w_out[:keep]
    return x + mm("bsf,fd->bsd", u, w_out, mode)


def loss_sums(conf: dict, params: dict, tokens, labels, *, mode: str = "f32",
              fault: str = "", constrain=lambda x: x):
    """(sum of token nll, sum of lse^2) over every position of the batch."""
    d = dims(conf)
    x = constrain(params["embed"]["tok"][tokens])
    tp_drop = fault == "no_exchange"

    @jax.checkpoint
    def body(x, lp):
        return constrain(_block(x, lp, d, mode, tp_drop)), None

    x, _ = jax.lax.scan(body, x, params["blocks"])
    x = rmsnorm(x, params["final_norm"]["scale"], d["eps"])
    w_head = params["embed"]["tok"].T if d["tied"] else params["embed"]["head"]
    return xent_blocks(x, w_head, labels, mode, min(LOSS_BLOCK, x.shape[1]))
