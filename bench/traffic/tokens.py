"""Seeded token rows, made on the host.

A draw is a pure function of (seed, stream, index): the same seed gives
the same rows in every run, and every row of every step differs.  The
unigram is Zipf-like (token id ~ V**u - 1 for uniform u), so the loss has
structure to learn and stays finite.
"""
from __future__ import annotations

import numpy as np


def rng(seed: int, *stream: int) -> np.random.Generator:
    return np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, *stream])


def rows(seed: int, stream: int, index: int, n: int, length: int,
         vocab: int) -> np.ndarray:
    """(n, length) int32 tokens below ``vocab``."""
    u = rng(seed, stream, index).random((n, length))
    return np.minimum(vocab ** u - 1.0, vocab - 1).astype(np.int32)
