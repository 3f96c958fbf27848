"""Shared set-up of the benchmark's own tests (run them with
``python3 -m pytest benchmarks/chip``): the benchmark and the program on
the path, and cells cut to a size the CPU runs in seconds."""
from __future__ import annotations

import copy
import dataclasses
import pathlib
import sys

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parents[1]
for p in (str(HERE), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

#: Widths of the small cells the CPU tests run; every mechanism of the real
#: cell is kept (GQA, qk-norm, tied or untied head, grouped SSD heads).
SMALL = {
    "mamba2": ({"d_model": 64, "n_layer": 2, "vocab_size": 500, "d_state": 16,
                "headdim": 16, "chunk_size": 32},
               {"num_layers": 2, "d_model": 64, "vocab_size": 512,
                "ssm_state": 16, "ssm_head_dim": 16},
               {"seq_len": 128, "global_batch": 1}),
    "dense": ({"hidden_size": 64, "num_hidden_layers": 2,
               "num_attention_heads": 4, "num_key_value_heads": 2,
               "head_dim": 16, "intermediate_size": 128, "vocab_size": 512},
              {"num_layers": 2, "d_model": 64, "num_heads": 4,
               "num_kv_heads": 2, "head_dim": 16, "d_ff": 128,
               "vocab_size": 512},
              {"seq_len": 128, "global_batch": 4}),
}


def small_cell(cell, single: bool = False, limits: dict | None = None):
    """``cell`` at the small widths of its reference family; ``single``
    replaces a searched plan by the one-device plan; ``limits`` replaces the
    cell's limits."""
    import harness

    conf = copy.deepcopy(cell.conf)
    sizes, program, traffic = SMALL[conf["reference"]]
    conf["config"].update(sizes)
    conf["program"]["set"].update(program)
    wl = dict(copy.deepcopy(cell.wl), **traffic)
    if single:
        wl["plan"] = {"kind": "single", "remat": "full"}
    return dataclasses.replace(cell, conf=conf, wl=wl,
                               limits=cell.limits if limits is None else limits)
