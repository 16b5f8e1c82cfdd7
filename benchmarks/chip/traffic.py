"""The one generator that turns a traffic file into inputs, from a seed.

Lengths come from clipped lognormal laws given by their median and sigma.
The set of sizes is drawn once from the file's ``sizes_seed``, so every run
seed gets the same sizes in another order: the work in a window does not
change with the seed, only which tokens fill it.

Token ids follow a Zipf law over the vocabulary, as text does, each document
with its own mapping of ranks to ids, so rows all differ.
"""
from __future__ import annotations

import numpy as np


def rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([abs(int(seed)), stream])


def lognormal(g: np.random.Generator, law: dict, n: int) -> np.ndarray:
    x = law["median"] * np.exp(law["sigma"] * g.standard_normal(n))
    return np.clip(np.rint(x), law["min"], law["max"]).astype(np.int64)


def zipf_tokens(g: np.random.Generator, n: int, vocab: int,
                a: float = 1.1) -> np.ndarray:
    """``n`` token ids: Zipf ranks mapped through a random shift."""
    ranks = np.minimum(g.zipf(a, n), vocab) - 1
    return ((ranks + g.integers(vocab)) % vocab).astype(np.int32)


class PackedRows:
    """``rows`` rows of ``seq_len`` tokens with next-token labels, made by
    packing documents end to end; ``example(i)`` is the interface of the
    program's datasets (``data/dataset.py``)."""

    def __init__(self, tr: dict, seed: int, vocab: int):
        seq, n_rows = tr["seq_len"], tr["rows"]
        need = n_rows * seq + 1
        lens = lognormal(rng(tr.get("sizes_seed", 0), 0), tr["documents"],
                         max(16, 4 * need // tr["documents"]["median"]))
        lens = lens[rng(seed, 1).permutation(len(lens))]
        ends = np.cumsum(lens)
        lens = lens[:int(np.searchsorted(ends, need)) + 1]
        g = rng(seed, 2)
        stream = np.concatenate([zipf_tokens(g, int(n), vocab)
                                 for n in lens])[:need]
        self.seq_len = seq
        self.inputs = stream[:-1].reshape(n_rows, seq)
        self.targets = stream[1:].reshape(n_rows, seq)

    def __len__(self):
        return len(self.inputs)

    def example(self, i: int) -> dict:
        return {"tokens": self.inputs[i], "labels": self.targets[i]}

