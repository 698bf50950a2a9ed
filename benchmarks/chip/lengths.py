"""Length draws shared by the traffic generators.

Every generator draws the *sizes* of its traffic from the mix file's
``shape_seed``, and only the token ids and the order within blocks of
``order_block`` consecutive items from the run's ``--seed``. So every
seed serves the same work, and the first n blocks of every seed's
backlog hold the same sizes: a window that sees only the head of a
backlog sees the same work whatever the seed.
"""
from __future__ import annotations

import numpy as np


def lognormal(rng: np.random.Generator, spec: dict, n: int) -> np.ndarray:
    """``n`` integer lengths: lognormal around ``median`` with log-sd
    ``sigma``, rounded and clipped to ``[min, max]``."""
    x = spec["median"] * np.exp(spec["sigma"] * rng.standard_normal(n))
    return np.clip(np.round(x), spec["min"], spec["max"]).astype(np.int64)


def uniform(rng: np.random.Generator, spec: dict, n: int) -> np.ndarray:
    """``n`` integers drawn uniformly from ``[min, max]``."""
    return rng.integers(spec["min"], spec["max"] + 1, size=n)


def length_range(spec: dict) -> tuple:
    return int(spec["min"]), int(spec["max"])


def block_order(rng: np.random.Generator, n: int, block: int) -> list:
    """``range(n)`` with each run of ``block`` consecutive indices
    permuted by ``rng``, the runs themselves in place."""
    out = []
    for b0 in range(0, n, block):
        out += [b0 + int(i) for i in rng.permutation(min(block, n - b0))]
    return out


def tokens(rng: np.random.Generator, vocab: int, n: int) -> np.ndarray:
    return rng.integers(0, vocab, size=int(n), dtype=np.int32)
