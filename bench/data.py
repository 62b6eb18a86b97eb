"""The token stream, as the benchmark's reference reads it.

A copy of the arithmetic of the program's synthetic data pipeline
(counter-based SplitMix64 hash of seed, step, row and position), kept here
so that the reference computes on the same tokens without importing the
program. Tokens are uniform over the vocabulary: every row of every step
differs, and every seed gives batches of one size.
"""
from __future__ import annotations

from typing import Tuple

import numpy as np

_MASK = 0xFFFFFFFFFFFFFFFF


def _splitmix64(x: np.ndarray) -> np.ndarray:
    x = (x + np.uint64(0x9E3779B97F4A7C15)) & np.uint64(_MASK)
    z = ((x ^ (x >> np.uint64(30))) * np.uint64(0xBF58476D1CE4E5B9)) \
        & np.uint64(_MASK)
    z = ((z ^ (z >> np.uint64(27))) * np.uint64(0x94D049BB133111EB)) \
        & np.uint64(_MASK)
    return z ^ (z >> np.uint64(31))


def batch(vocab: int, rows: int, seq: int, seed: int, step: int
          ) -> Tuple[np.ndarray, np.ndarray]:
    """(tokens, labels), each (rows, seq) int32, of ``step`` of the stream
    that ``seed`` names."""
    r = np.arange(rows, dtype=np.uint64)[:, None]
    c = np.arange(seq + 1, dtype=np.uint64)[None, :]
    key = np.uint64((seed * 1_000_003 + step * 0xD1B54A32D192ED03) & _MASK)
    with np.errstate(over="ignore"):
        raw = _splitmix64(key + r * np.uint64(0x100000001B3) + c)
    toks = (raw % np.uint64(vocab)).astype(np.int32)
    return toks[:, :-1], toks[:, 1:]


def stream_seed(seed: int, call: int) -> int:
    """The data seed of the ``call``-th run of the trainer in a benchmark
    run: each call restarts at step 0, so each gets its own stream."""
    return seed * 8 + call
