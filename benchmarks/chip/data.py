"""The seeded synthetic token stream every training cell is fed.

The same arithmetic as the trainer's ``SyntheticDataset`` at the time the
benchmark was written, kept here so that a change to the program cannot
change the inputs: sample ``i`` of seed ``s`` is drawn from its own Philox
stream keyed by ``s * 1_000_003 + i``, uniformly over ``[0, vocab)``, one
token longer than the sequence, and split into inputs and next-token labels.
Step ``t`` of a global batch ``B`` holds samples ``t*B .. t*B+B-1``, so no
two rows of a run repeat.
"""
from __future__ import annotations

import numpy as np


def batch(seed: int, step: int, global_batch: int, seq_len: int,
          vocab: int) -> dict:
    ids = np.arange(step * global_batch, (step + 1) * global_batch,
                    dtype=np.int64)
    toks = np.empty((global_batch, seq_len + 1), np.int32)
    for row, sid in enumerate(ids):
        g = np.random.Generator(np.random.Philox(
            key=int(seed) * 1_000_003 + int(sid)))
        toks[row] = g.integers(0, vocab, seq_len + 1, dtype=np.int32)
    return {"tokens": toks[:, :-1], "labels": toks[:, 1:].copy()}
