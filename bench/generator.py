"""The one generator of training traffic: token batches from a traffic
file's parameters and the seed.

A copy of the program's synthetic Markov-Zipf source (each next token is
a fixed random bigram successor, or with probability 0.3 a Zipf draw), kept
here so that the reference's batches do not come from the code under test.
A batch is a pure function of (seed, step).
"""
from __future__ import annotations

import numpy as np


class MarkovZipf:
    def __init__(self, *, seq_len, global_batch, vocab_size, seed, zipf_a,
                 noise=0.3):
        self.seq_len, self.batch = seq_len, global_batch
        self.vocab, self.seed, self.a, self.noise = (vocab_size, seed, zipf_a,
                                                     noise)
        rng = np.random.default_rng(seed)
        self.next_tok = rng.integers(0, vocab_size, size=(vocab_size,),
                                     dtype=np.int64)

    def batch_at(self, step: int) -> dict:
        rng = np.random.default_rng((self.seed, step))
        B, S, V = self.batch, self.seq_len, self.vocab
        start = rng.integers(0, V, size=(B,))
        noise = (rng.zipf(self.a, size=(B, S + 1)) - 1) % V
        use_noise = rng.random((B, S + 1)) < self.noise
        toks = np.empty((B, S + 1), dtype=np.int64)
        toks[:, 0] = start
        for t in range(1, S + 1):
            det = self.next_tok[toks[:, t - 1]]
            toks[:, t] = np.where(use_noise[:, t], noise[:, t], det)
        return {"tokens": toks[:, :-1].astype(np.int32),
                "labels": toks[:, 1:].astype(np.int32)}


def make(traffic: dict, *, vocab_size: int, seed: int):
    if traffic["source"] != "synthetic":
        raise ValueError(f"no generator for source {traffic['source']!r}")
    return MarkovZipf(seq_len=traffic["seq_len"],
                      global_batch=traffic["global_batch"],
                      vocab_size=vocab_size, seed=seed,
                      zipf_a=traffic["zipf_a"])
