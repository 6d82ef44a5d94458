"""The one general generator of a training job's rows.

A job is a data file under ``benchmark/traffic/``: sequence length, rows a
chip and a step, distinct batches. Every seed gets the same amount of work
(the same shapes and counts) and its own token ids.
"""
from __future__ import annotations

import numpy as np


def train_rows(job: dict, seed: int, chips: int, vocab: int) -> np.ndarray:
    """``[rows, seq_len + 1]`` token ids in ``1 .. vocab - 1`` (0 is the
    criterion's padding value): rows that all differ."""
    rows = job["dataset_batches"] * job["batch_per_chip"] * chips
    rng = np.random.default_rng(int(seed))
    return rng.integers(1, vocab, size=(rows, job["seq_len"] + 1),
                        dtype=np.int64).astype(np.int32)
