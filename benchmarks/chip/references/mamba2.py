"""Plain float32 reference of a Mamba2 language model.

Blocks: RMSNorm -> projections z, x, B, C, dt -> causal depthwise conv (taps
stored newest first) and SiLU on x, B, C -> SSD with A = -exp(A_log) and
dt = softplus(dt_raw + dt_bias) -> D skip -> RMSNorm(y * silu(z)) over the
whole inner width (one group) -> out projection -> residual; final RMSNorm;
LM head tied to the embedding.  The SSD is the paper's minimal blocked
algorithm (``ssd_minimal_discrete``, arXiv:2405.21060 listing 1) at the
published block length, computed in float32; keys are the published
``config.json`` keys plus the Mamba2 module's defaults.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from references.common import HIGHEST, mm, rmsnorm, xent_blocks
from weights import Leaf

LOSS_BLOCK = 512
#: the configuration key that counts the layers (the depth cut)
DEPTH_KEY = "n_layer"


def dims(conf: dict) -> dict:
    D = conf["d_model"]
    Di = conf["expand"] * D
    mult = conf["pad_vocab_size_multiple"]
    return {"D": D, "L": conf["n_layer"], "Di": Di, "P": conf["headdim"],
            "H": Di // conf["headdim"], "G": conf["ngroups"],
            "N": conf["d_state"], "W": conf["d_conv"],
            "Q": conf["chunk_size"], "eps": conf["norm_epsilon"],
            "V": -(-conf["vocab_size"] // mult) * mult,
            "tied": conf["tie_embeddings"]}


def token_vocab(conf: dict) -> int:
    """Tokens are drawn from the real vocabulary, not its padding rows."""
    return conf["vocab_size"]


def program_fields(conf: dict) -> dict:
    d = dims(conf)
    return {"family": "ssm", "d_model": d["D"], "num_layers": d["L"],
            "vocab_size": d["V"], "ssm_state": d["N"],
            "ssm_expand": conf["expand"], "ssm_head_dim": d["P"],
            "ssm_groups": d["G"], "conv_width": d["W"], "norm_eps": d["eps"],
            "tie_embeddings": d["tied"]}


def param_spec(conf: dict) -> dict:
    d = dims(conf)
    D, L, Di, H, G, N, W, V = (d[k] for k in ("D", "L", "Di", "H", "G", "N",
                                                "W", "V"))
    embed = {"tok": Leaf((V, D), std=0.02)}
    if not d["tied"]:
        embed["head"] = Leaf((D, V), std=D ** -0.5)
    return {
        "embed": embed,
        "blocks": {
            "ln": {"scale": Leaf((L, D), "ones")},
            "w_z": Leaf((L, D, Di), std=D ** -0.5),
            "w_x": Leaf((L, D, Di), std=D ** -0.5),
            "w_B": Leaf((L, D, G * N), std=D ** -0.5),
            "w_C": Leaf((L, D, G * N), std=D ** -0.5),
            "w_dt": Leaf((L, D, H), std=D ** -0.5),
            "conv_x": Leaf((L, W, Di), std=0.5),
            "conv_B": Leaf((L, W, G * N), std=0.5),
            "conv_C": Leaf((L, W, G * N), std=0.5),
            "A_log": Leaf((L, H), "a_log"),
            "D": Leaf((L, H), "ones"),
            "dt_bias": Leaf((L, H), "dt_bias"),
            "gate_norm": {"scale": Leaf((L, Di), "ones")},
            "w_out": Leaf((L, Di, D), std=Di ** -0.5),
        },
        "final_norm": {"scale": Leaf((D,), "ones")},
    }


def _conv(x, w):
    """Causal depthwise conv; x (B, S, C), w (W, C), w[0] on the newest."""
    W = w.shape[0]
    pad = jnp.pad(x, ((0, 0), (W - 1, 0), (0, 0)))
    S = x.shape[1]
    return sum(pad[:, W - 1 - j:W - 1 - j + S] * w[j] for j in range(W))


def _segsum(x):
    """x (..., T) -> (..., T, T): sum of x over (j, i] below the diagonal,
    -inf above it."""
    T = x.shape[-1]
    xx = jnp.broadcast_to(x[..., None], x.shape + (T,))
    xx = jnp.where(jnp.tril(jnp.ones((T, T), bool), -1), xx, 0.0)
    ss = jnp.cumsum(xx, axis=-2)
    return jnp.where(jnp.tril(jnp.ones((T, T), bool), 0), ss, -jnp.inf)


def ssd(X, A, B, C, block: int):
    """X (b, l, h, p) = x*dt; A (b, l, h) = A*dt; B, C (b, l, h, n)."""
    b, l, h, p = X.shape
    c = l // block
    X, B, C = (t.reshape(b, c, block, *t.shape[2:]) for t in (X, B, C))
    A = jnp.moveaxis(A.reshape(b, c, block, h), -1, 1)        # b h c l
    A_cs = jnp.cumsum(A, axis=-1)
    e = lambda s, *ops: jnp.einsum(s, *ops, precision=HIGHEST)
    Y_diag = e("bclhn,bcshn,bhcls,bcshp->bclhp", C, B, jnp.exp(_segsum(A)), X)
    decay_states = jnp.exp(A_cs[..., -1:] - A_cs)
    states = e("bclhn,bhcl,bclhp->bchpn", B, decay_states, X)
    states = jnp.concatenate([jnp.zeros_like(states[:, :1]), states], axis=1)
    decay_chunk = jnp.exp(_segsum(jnp.pad(A_cs[..., -1], ((0, 0), (0, 0), (1, 0)))))
    states = e("bhzc,bchpn->bzhpn", decay_chunk, states)[:, :-1]
    Y_off = e("bclhn,bchpn,bhcl->bclhp", C, states, jnp.exp(A_cs))
    return (Y_diag + Y_off).reshape(b, l, h, p)


def _block(x, lp, d, mode):
    Bsz, S, _ = x.shape
    H, G, N, P = d["H"], d["G"], d["N"], d["P"]
    h = rmsnorm(x, lp["ln"]["scale"], d["eps"])
    z = mm("bsd,di->bsi", h, lp["w_z"], mode)
    xv = jax.nn.silu(_conv(mm("bsd,di->bsi", h, lp["w_x"], mode), lp["conv_x"]))
    Bv = jax.nn.silu(_conv(mm("bsd,dg->bsg", h, lp["w_B"], mode), lp["conv_B"]))
    Cv = jax.nn.silu(_conv(mm("bsd,dg->bsg", h, lp["w_C"], mode), lp["conv_C"]))
    dt = jax.nn.softplus(mm("bsd,dh->bsh", h, lp["w_dt"], mode) + lp["dt_bias"])
    A = -jnp.exp(lp["A_log"])
    xh = xv.reshape(Bsz, S, H, P)
    rep = H // G
    Bh = jnp.repeat(Bv.reshape(Bsz, S, G, N), rep, axis=2)
    Ch = jnp.repeat(Cv.reshape(Bsz, S, G, N), rep, axis=2)
    y = ssd(xh * dt[..., None], A * dt, Bh, Ch, min(d["Q"], S))
    y = (y + lp["D"][:, None] * xh).reshape(Bsz, S, d["Di"])
    y = rmsnorm(y * jax.nn.silu(z), lp["gate_norm"]["scale"], d["eps"])
    return x + mm("bsi,id->bsd", y, lp["w_out"], mode)


def loss_sums(conf: dict, params: dict, tokens, labels, *, mode: str = "f32",
              fault: str = "", constrain=lambda x: x):
    """(sum of token nll, sum of lse^2) over every position of the batch."""
    if fault:
        raise ValueError(f"fault {fault!r} does not apply to one chip")
    d = dims(conf)
    x = constrain(params["embed"]["tok"][tokens])

    @jax.checkpoint
    def body(x, lp):
        return constrain(_block(x, lp, d, mode)), None

    x, _ = jax.lax.scan(body, x, params["blocks"])
    x = rmsnorm(x, params["final_norm"]["scale"], d["eps"])
    w_head = params["embed"]["tok"].T if d["tied"] else params["embed"]["head"]
    return xent_blocks(x, w_head, labels, mode, min(LOSS_BLOCK, x.shape[1]))
