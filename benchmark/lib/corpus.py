"""Seeded Markov corpus: the benchmark's inputs.

Copied from ``experiments/lm/data.py`` (PR 21 tree) so that no later PR
changes what the cells are fed; the original stays the trainer CLI's.
An order-1 Markov chain over 256 byte-like ids whose transition table comes
from a fixed PRNG: learnable structure, so a falling loss means something.
"""

from __future__ import annotations

from typing import Iterator, Tuple

import numpy as np

VOCAB = 256
BRANCHING = 8


def generate_corpus(n_tokens: int, seed: int = 0) -> np.ndarray:
    """Deterministic token stream ``[n_tokens] int32`` (ids < 256)."""
    rng = np.random.RandomState(seed)
    table = rng.randint(0, VOCAB, size=(VOCAB, BRANCHING))
    rng = np.random.RandomState(seed + 1)
    state = int(rng.randint(0, VOCAB))
    choices = rng.randint(0, BRANCHING, size=n_tokens)
    out = np.empty(n_tokens, np.int32)
    for i in range(n_tokens):
        state = table[state, choices[i]]
        out[i] = state
    return out


def batch_at(corpus: np.ndarray, batch: int, seq: int,
             rng: np.random.Generator) -> Tuple[np.ndarray, np.ndarray]:
    """One random-offset next-token batch: x, y ``[batch, seq] int32``."""
    starts = rng.integers(0, len(corpus) - seq - 1, size=batch)
    windows = np.stack([corpus[s:s + seq + 1] for s in starts])
    return (np.ascontiguousarray(windows[:, :-1], np.int32),
            np.ascontiguousarray(windows[:, 1:], np.int32))


def batches(corpus: np.ndarray, batch: int, seq: int,
            seed: int) -> Iterator[Tuple[np.ndarray, np.ndarray]]:
    """Endless stream of same-shaped batches drawn from ``seed``."""
    rng = np.random.default_rng(seed)
    while True:
        yield batch_at(corpus, batch, seq, rng)
