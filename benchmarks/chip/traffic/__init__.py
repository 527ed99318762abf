"""The one traffic generator: token batches for a training cell, drawn from
`--seed`, shaped by a traffic file in this directory (``<name>.json``).

A copy of the program's synthetic stream (``repro.data.pipeline.SyntheticLM``):
Zipf-distributed token ids over the real vocabulary, with document
boundaries (the ``bos_id`` token) at uniformly drawn positions, one
independent stream per step seeded by ``SeedSequence([seed, step])``.  Every
seed gives the same shapes and the same amount of work; only the ids differ.

A traffic file holds::

    {"kind": "train", "seq_len": 16384, "batch": 1,
     "zipf_a": 1.2, "mean_doc_len": 512, "bos_id": 1}
"""
from __future__ import annotations

import numpy as np

KEYS = ("kind", "seq_len", "batch", "zipf_a", "mean_doc_len", "bos_id")


class TokenFeed:
    """``batch(step) -> (tokens, labels)``, int32 arrays of shape
    [batch, seq_len]; labels are the tokens shifted by one."""

    def __init__(self, traffic: dict, vocab_size: int, seed: int):
        missing = [k for k in KEYS if k not in traffic]
        if missing:
            raise ValueError(f"traffic file lacks {missing}")
        if traffic["kind"] != "train":
            raise ValueError(f"traffic kind {traffic['kind']!r}: only "
                             f"'train' has a generator")
        self.seq = int(traffic["seq_len"])
        self.rows = int(traffic["batch"])
        self.zipf_a = float(traffic["zipf_a"])
        self.mean_doc = int(traffic["mean_doc_len"])
        self.bos = int(traffic["bos_id"])
        self.vocab = int(vocab_size)
        self.seed = int(seed)

    @property
    def tokens_per_step(self) -> int:
        return self.rows * self.seq

    def batch(self, step: int):
        rng = np.random.default_rng(np.random.SeedSequence([self.seed, step]))
        toks = rng.zipf(self.zipf_a, size=(self.rows, self.seq + 1))
        toks = np.minimum(toks + 1, self.vocab - 1).astype(np.int32)
        n_docs = max(1, self.seq // self.mean_doc)
        for b in range(self.rows):
            toks[b, rng.integers(0, self.seq, size=n_docs)] = self.bos
        return (np.ascontiguousarray(toks[:, :-1]),
                np.ascontiguousarray(toks[:, 1:]))
