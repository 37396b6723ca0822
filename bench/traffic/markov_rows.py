"""The one generator of training traffic: rows of a sparse Markov chain.

A copy of the program's ``synthetic_batches`` (``train/data.py``), kept
here so that the traffic cannot move with the program: each token has
``successors`` preferred next tokens drawn from the seed, and every row
starts at a random token.  A traffic file gives ``batch``, ``seq``,
``successors`` and ``pool``; ``pool`` distinct batches are made at set-up
and fed in turn.
"""

from __future__ import annotations

import numpy as np


def pool(params: dict, vocab: int, seed: int) -> list[dict]:
    """``params["pool"]`` batches of ``{"tokens", "labels"}`` int32 arrays
    of shape (batch, seq); the same seed gives the same batches."""
    rng = np.random.default_rng(seed)
    b, t, n = params["batch"], params["seq"], params["pool"]
    fan = params["successors"]
    succ = rng.integers(0, vocab, size=(vocab, fan), dtype=np.int32)
    rows = np.empty((n * b, t + 1), np.int32)
    rows[:, 0] = rng.integers(0, vocab, size=n * b)
    choice = rng.integers(0, fan, size=(n * b, t))
    for i in range(t):
        rows[:, i + 1] = succ[rows[:, i], choice[:, i]]
    rows = rows.reshape(n, b, t + 1)
    return [{"tokens": np.ascontiguousarray(r[:, :-1]),
             "labels": np.ascontiguousarray(r[:, 1:])} for r in rows]
