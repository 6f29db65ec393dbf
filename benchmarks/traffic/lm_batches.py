"""Training traffic: token rows for next-token prediction, from the seed.

Parameters (a traffic file with `"generator": "lm_batches"`): `batch` rows
per step over the whole cell, `seq_len` tokens per row, `rows` in the data
set (more than any window consumes, so no epoch ends inside one), `shuffle`.

Rows follow a fixed permutation of the vocabulary from a seeded start token
(copied from tpudml.data.datasets.synthetic_lm, so that a later PR to the
program cannot change the data): learnable, every row different as long as
start tokens differ, and drawn without replacement so that they do."""

from __future__ import annotations

import numpy as np


def make(params: dict, config: dict, seed: int) -> dict:
    """``{"inputs": [rows, T] int32, "targets": [rows, T] int32}``."""
    rows, seq_len, vocab = params["rows"], params["seq_len"], config["vocab_size"]
    if rows > vocab:
        raise ValueError("rows must not exceed the vocabulary: rows would repeat")
    perm = np.random.default_rng(0xC0FFEE).permutation(vocab)
    rng = np.random.default_rng(seed)
    seqs = np.empty((rows, seq_len + 1), np.int32)
    seqs[:, 0] = rng.choice(vocab, size=rows, replace=False)
    for t in range(seq_len):
        seqs[:, t + 1] = perm[seqs[:, t]]
    return {"inputs": seqs[:, :-1], "targets": seqs[:, 1:]}
