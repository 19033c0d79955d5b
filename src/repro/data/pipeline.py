"""Data pipeline: deterministic, stateless, resumable.

Every batch is a pure function of (seed, step) — exact fault-tolerant resume
needs only the step counter from the checkpoint (DESIGN.md §5), and any host
can (re)compute any shard, which is what elastic re-scaling requires.

Two sources behind one interface:
  * SyntheticLM   — Zipf-distributed tokens with a Markov structure, so the
    loss actually *decreases* under training (used by tests/benchmarks; the
    paper's OpenWebText/Pile are not available offline).
  * MemmapTokens  — binary uint16/uint32 token files (the nanoGPT format the
    paper uses: train.bin / val.bin), memory-mapped, random offsets per step.

Per-host sharding: ``host_slice`` gives each process only its slice of the
global batch (process_index-strided), matching jax.make_array_from_callback.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Iterator, Optional

import jax
import numpy as np


#: a host span around each batch, recorded when a profile is being taken
#: (``jax.profiler``), on the clock of the device trace
_batch_span = functools.partial(jax.profiler.annotate_function,
                                name="data.batch_at")


@dataclasses.dataclass
class DataConfig:
    seq_len: int
    global_batch: int
    vocab_size: int
    seed: int = 0
    source: str = "synthetic"          # synthetic | memmap
    path: Optional[str] = None         # for memmap
    zipf_a: float = 1.2                # synthetic skew


class SyntheticLM:
    """Markov-Zipf synthetic LM stream.

    Token t+1 = (a * t + noise) mod V with Zipf-distributed resets: gives
    learnable bigram structure (optimizers separate cleanly on it) while
    staying O(1) memory.
    """

    def __init__(self, cfg: DataConfig):
        self.cfg = cfg
        # fixed random bigram table (small vocab) for learnable structure
        rng = np.random.default_rng(cfg.seed)
        self.next_tok = rng.integers(0, cfg.vocab_size,
                                     size=(cfg.vocab_size,), dtype=np.int64)

    @_batch_span
    def batch_at(self, step: int) -> dict:
        cfg = self.cfg
        rng = np.random.default_rng((cfg.seed, step))
        B, S = cfg.global_batch, cfg.seq_len
        # 70% deterministic bigram transitions, 30% Zipf noise
        start = rng.integers(0, cfg.vocab_size, size=(B,))
        noise = (rng.zipf(cfg.zipf_a, size=(B, S + 1)) - 1) % cfg.vocab_size
        use_noise = rng.random((B, S + 1)) < 0.3
        toks = np.empty((B, S + 1), dtype=np.int64)
        toks[:, 0] = start
        for t in range(1, S + 1):
            det = self.next_tok[toks[:, t - 1]]
            toks[:, t] = np.where(use_noise[:, t], noise[:, t], det)
        return {"tokens": toks[:, :-1].astype(np.int32),
                "labels": toks[:, 1:].astype(np.int32)}


class MemmapTokens:
    """nanoGPT-style binary token file (the paper's data format)."""

    def __init__(self, cfg: DataConfig, dtype=np.uint16):
        self.cfg = cfg
        self.data = np.memmap(cfg.path, dtype=dtype, mode="r")

    @_batch_span
    def batch_at(self, step: int) -> dict:
        cfg = self.cfg
        rng = np.random.default_rng((cfg.seed, step))
        B, S = cfg.global_batch, cfg.seq_len
        ix = rng.integers(0, len(self.data) - S - 1, size=(B,))
        toks = np.stack([self.data[i:i + S + 1].astype(np.int32) for i in ix])
        return {"tokens": toks[:, :-1], "labels": toks[:, 1:]}


def make_source(cfg: DataConfig):
    if cfg.source == "memmap":
        return MemmapTokens(cfg)
    return SyntheticLM(cfg)


def host_slice(batch: dict, process_index: int, process_count: int) -> dict:
    """This host's strided slice of the global batch."""
    return {k: v[process_index::process_count] for k, v in batch.items()}


def iterate(source, start_step: int = 0) -> Iterator[dict]:
    step = start_step
    while True:
        yield source.batch_at(step)
        step += 1
