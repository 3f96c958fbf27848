"""Deterministic synthetic data pipeline + abstract input specs.

``input_specs`` returns ``jax.ShapeDtypeStruct`` stand-ins for every model
input of a (config × shape) cell — the dry-run lowers against these, so no
full-size array is ever allocated.  ``SyntheticDataset`` produces the same
token stream for a given (seed, host, step) triple regardless of world size,
which is what makes elastic restarts and straggler-tolerant data serving
reproducible: a host only ever materializes its own shard.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.arch import INPUT_LEN, arch_of
from repro.configs.registry import ModelConfig
from repro.configs.shapes import ShapeSpec


#: dtype of the precomputed embedding inputs (vis_embeds / audio frames).
#: Must match between ``input_specs`` (what the dry-run lowers against) and
#: ``SyntheticDataset.batch`` (what the real step is fed) — a mismatch means
#: the lowered executable never sees the shapes/dtypes that actually arrive.
EMBED_DTYPE = jnp.bfloat16

#: Philox stream-id word for audio frames: keyed per (seed, sample id) just
#: like the token stream, but on a distinct stream so frames and tokens of
#: the same sample draw independent bits.
_FRAMES_STREAM = 7


def _text_len(cfg: ModelConfig, seq_len: int) -> int:
    if "vis_embeds" in arch_of(cfg).inputs:
        return seq_len - cfg.vis_tokens
    return seq_len


def _embed_inputs(cfg: ModelConfig, batch: int) -> dict:
    """The architecture's embedding inputs: name -> (batch, length, d_model)."""
    return {k: (batch, getattr(cfg, INPUT_LEN[k]), cfg.d_model)
            for k in arch_of(cfg).inputs}


def input_specs(cfg: ModelConfig, shape: ShapeSpec, model=None) -> dict:
    """Abstract inputs for train/prefill/decode lowering."""
    B, S = shape.global_batch, shape.seq_len
    if shape.kind in ("train", "prefill"):
        st = _text_len(cfg, S)
        out = {"tokens": jax.ShapeDtypeStruct((B, st), jnp.int32)}
        if shape.kind == "train":
            out["labels"] = jax.ShapeDtypeStruct((B, st), jnp.int32)
        for k, shp in _embed_inputs(cfg, B).items():
            out[k] = jax.ShapeDtypeStruct(shp, EMBED_DTYPE)
        return out
    if shape.kind == "decode":
        assert model is not None, "decode specs need the model for its cache pytree"
        return {
            "tokens": jax.ShapeDtypeStruct((B, 1), jnp.int32),
            "cache": model.abstract_cache(B, S),
            "cache_index": jax.ShapeDtypeStruct((), jnp.int32),
            "kv_len": jax.ShapeDtypeStruct((B,), jnp.int32),
        }
    raise ValueError(shape.kind)


# --------------------------------------------------------------------------
# synthetic stream
# --------------------------------------------------------------------------

@dataclasses.dataclass
class SyntheticDataset:
    """Deterministic LM data: next-token prediction over a hashed stream.

    The stream for global sample ``i`` depends only on (seed, i), so any
    host/worker layout yields identical global batches — resharding after an
    elastic event never replays or skips data.
    """

    cfg: ModelConfig
    seq_len: int
    global_batch: int
    seed: int = 0

    def _tokens(self, sample_ids: np.ndarray) -> np.ndarray:
        st = _text_len(self.cfg, self.seq_len)
        # per-sample independent Philox streams keyed by sample id
        out = np.empty((len(sample_ids), st + 1), np.int32)
        for row, sid in enumerate(sample_ids):
            g = np.random.Generator(np.random.Philox(key=self.seed * 1_000_003 + int(sid)))
            out[row] = g.integers(0, self.cfg.vocab_size, st + 1, dtype=np.int32)
        return out

    def global_ids(self, step: int) -> np.ndarray:
        start = step * self.global_batch
        return np.arange(start, start + self.global_batch, dtype=np.int64)

    def batch(self, step: int, host_id: int = 0, num_hosts: int = 1) -> dict:
        """Host-local shard of the global batch (rows host_id::num_hosts)."""
        ids = self.global_ids(step)[host_id::num_hosts]
        toks = self._tokens(ids)
        batch = {
            "tokens": toks[:, :-1],
            "labels": toks[:, 1:].copy(),
        }
        embed_dtype = np.dtype(EMBED_DTYPE)   # match input_specs exactly
        for k, shp in _embed_inputs(self.cfg, len(ids)).items():
            batch[k] = np.zeros(shp, embed_dtype)      # vis_embeds stay zero
        if "frames" in batch:
            # per-sample Philox streams, like _tokens: frame content follows
            # the sample id, so any host layout yields the same global batch
            # and no two steps repeat frames
            for row, sid in enumerate(ids):
                g = np.random.Generator(np.random.Philox(
                    key=[self.seed * 1_000_003 + int(sid), _FRAMES_STREAM]))
                batch["frames"][row] = g.standard_normal(
                    batch["frames"].shape[1:]).astype(embed_dtype)
        return batch
