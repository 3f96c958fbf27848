"""Model FLOPs of one trained token, counted from a configuration's sizes.

Training counts 3x the forward pass (forward, and a backward of twice its
work).  Recomputation is never counted.

* Each parameter that multiplies an activation counts 6 FLOPs a token: the
  projections, the MLP, depthwise conv taps and the LM head.  The embedding
  gather multiplies nothing and is left out; so are norm scales and other
  per-channel vectors, whose work is elementwise.
* Attention adds its score and value products over the causal half: per
  layer and token, 2 products x 2 FLOPs x heads x head_dim x S/2 forward.
* The Mamba2 SSD adds the blocked algorithm's contractions (Dao and Gu,
  arXiv:2405.21060, listing 1, B and C expanded to every head as there) at
  the published block length, whatever block the program runs: per head and
  token, the in-block scores (Q/2 x N) and their product with x (Q/2 x P)
  over the causal half of a block, the block states (N x P) and the states'
  read-out (N x P).

A reference module with a family this file does not count may carry its own
``model_flops_per_token(conf, seq_len)``.
"""
from __future__ import annotations

import importlib


def dense(conf: dict, seq_len: int) -> float:
    D, L = conf["hidden_size"], conf["num_hidden_layers"]
    H, KV, hd = (conf["num_attention_heads"], conf["num_key_value_heads"],
                 conf["head_dim"])
    F, V = conf["intermediate_size"], conf["vocab_size"]
    per_layer = D * H * hd + 2 * D * KV * hd + H * hd * D + 3 * D * F
    matmul = 6.0 * (L * per_layer + D * V)
    attention = 3.0 * L * (2 * 2 * H * hd * seq_len / 2)
    return matmul + attention


def mamba2(conf: dict, seq_len: int) -> float:
    D, L = conf["d_model"], conf["n_layer"]
    Di = conf["expand"] * D
    H, N, P = Di // conf["headdim"], conf["d_state"], conf["headdim"]
    GN = conf["ngroups"] * N
    W = conf["d_conv"]
    Q = conf["chunk_size"]
    mult = conf["pad_vocab_size_multiple"]
    V = -(-conf["vocab_size"] // mult) * mult
    per_layer = D * (2 * Di + 2 * GN + H) + Di * D + W * (Di + 2 * GN)
    matmul = 6.0 * (L * per_layer + D * V)
    ssd_macs = H * (Q / 2 * N + Q / 2 * P + N * P + N * P)
    return matmul + 3.0 * L * 2 * ssd_macs


COUNTERS = {"dense": dense, "mamba2": mamba2}


def per_token(conf: dict, seq_len: int) -> float:
    """FLOPs of one trained token for configuration file ``conf`` (its
    ``config`` holds the sizes, ``reference`` names the family)."""
    family = conf["reference"]
    fn = COUNTERS.get(family)
    if fn is None:
        fn = importlib.import_module(f"references.{family}").model_flops_per_token
    return fn(conf["config"], seq_len)
