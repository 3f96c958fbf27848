"""One description of each architecture: what the planner, the verifier, the
runtime and the launchers may do with a ``ModelConfig.family``.

``arch_of(cfg)`` is the only reader of ``cfg.family`` in the program (the
``family-read`` lint rule holds the tree to it).  Every other module asks
the :class:`Arch` it returns, or walks ``layer_slots(cfg)`` and branches on
a layer's kind, which is a property of the layer, not of the family.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, NamedTuple, Optional


class Slot(NamedTuple):
    """One layer of the model, in execution order."""
    kind: str                     # attn_block | moe_block | mamba_block | enc_block | dec_block
    name: str
    shared: Optional[str] = None  # same string => weights shared across slots
    seq: Optional[str] = None     # config field of its sequence length (None: the step's)


#: batch entries besides tokens/labels -> config field of their length;
#: each is a (batch, length, d_model) embedding input
INPUT_LEN = {"vis_embeds": "vis_tokens", "frames": "enc_frames"}
#: layer kinds that keep a decode KV cache over the step's sequence
KV_KINDS = ("attn_block", "moe_block", "dec_block")


@dataclasses.dataclass(frozen=True)
class Arch:
    model: str                    # "module:Class" that build_model constructs
    block_kind: str               # the repeated block; a non-stacked model runs
                                  # this kind's majority strategy everywhere
    slots: Optional[Callable] = None   # cfg -> tuple[Slot, ...]; None: one
                                       # stack of num_layers block_kind layers
    cp: bool = False              # ring attention is implemented
    sp: bool = True               # sequence parallelism is legal
    paged_serving: bool = False   # the paged KV-cache serving path takes it
    sub_quadratic: bool = False   # decode state grows sub-quadratically
    inputs: tuple = ()            # extra batch entries (INPUT_LEN keys)

    @property
    def stacked(self) -> bool:
        """One homogeneous scanned stack: the runtime may group or split it."""
        return self.slots is None

    @property
    def pp(self) -> bool:
        """The pipeline runtime stages it (a stack without expert dispatch)."""
        return self.stacked and self.block_kind != "moe_block"


def _hybrid(cfg) -> tuple:
    out = []
    for i in range(cfg.num_layers):
        out.append(Slot("mamba_block", f"mamba{i}"))
        if (i + 1) % cfg.attn_every == 0:
            out.append(Slot("attn_block", f"shared_attn@{i}", "shared_attn"))
    return tuple(out)


def _encdec(cfg) -> tuple:
    return (tuple(Slot("enc_block", f"enc{i}", seq="enc_frames")
                  for i in range(cfg.enc_layers))
            + tuple(Slot("dec_block", f"dec{i}") for i in range(cfg.num_layers)))


_M = "repro.models."
ARCHS = {
    "dense": Arch(_M + "transformer:DenseTransformerLM", "attn_block",
                  cp=True, paged_serving=True),
    "vlm": Arch(_M + "transformer:VLMTransformerLM", "attn_block",
                inputs=("vis_embeds",)),
    "moe": Arch(_M + "moe:MoETransformerLM", "moe_block"),
    "ssm": Arch(_M + "mamba2:Mamba2LM", "mamba_block", sp=False,  # SSD scan is
                sub_quadratic=True),                            # sequential
    "hybrid": Arch(_M + "hybrid:HybridLM", "mamba_block", _hybrid,
                   sub_quadratic=True),
    "audio": Arch(_M + "encdec:EncDecLM", "dec_block", _encdec, inputs=("frames",)),
}


def arch_of(cfg) -> Arch:
    if cfg.family not in ARCHS:
        raise ValueError(f"unknown family {cfg.family!r}")
    return ARCHS[cfg.family]


def layer_slots(cfg) -> tuple:
    a = arch_of(cfg)
    return (a.slots(cfg) if a.slots else
            tuple(Slot(a.block_kind, f"layer{i}") for i in range(cfg.num_layers)))
