"""Training batches from the seed: one general generator, parameters per cell.

A batch is a pure function of (seed, step), so the same seed gives the same
rows and every step's rows differ.  Tokens follow a Zipf(1.3) unigram mix in
which half the positions take a fixed successor of the token before, so the
loss has something to learn.  (The same arithmetic as the program's
``data/synthetic.py``; kept here so that no change to the program moves the
benchmark's inputs.)
"""
from __future__ import annotations

import numpy as np


def batch(vocab: int, rows: int, seq: int, *, seed: int, step: int) -> dict:
    """``{"tokens", "labels"}``, each int32 [rows, seq]."""
    rng = np.random.default_rng(np.random.SeedSequence([seed, step]))
    base = rng.zipf(1.3, size=(rows, seq + 1)).astype(np.int64) % vocab
    succ = (np.arange(vocab) * 31 + 7) % vocab
    follow = rng.random((rows, seq + 1)) < 0.5
    toks = base.copy()
    toks[:, 1:] = np.where(follow[:, 1:], succ[toks[:, :-1]], base[:, 1:])
    return {"tokens": toks[:, :seq].astype(np.int32),
            "labels": toks[:, 1:seq + 1].astype(np.int32)}
