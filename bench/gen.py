"""Token generator of the benchmark's traffic mixes.

A mix file (``bench/traffic/<name>.json``) gives the batch and the sequence
length. The tokens follow the one rule the system's training loop feeds a
token model with (`repro.data.synthetic.SyntheticStream`), which the
harness cannot choose: every token drawn uniformly from the vocabulary by
NumPy's PCG64 seeded with ``(seed << 32) ^ step``, the labels shifted by
one position. It is a pure function of (seed, step), so the stream a run
trains on can be made again here for the reference. Every row of every
step differs.
"""
from __future__ import annotations

import numpy as np


def batch_at(traffic: dict, vocab_size: int, seed: int, step: int):
    """(tokens, labels) of step ``step`` (0-based), each (batch, seq) int32."""
    b, s = traffic["batch"], traffic["seq"]
    rng = np.random.default_rng((seed << 32) ^ step)
    toks = rng.integers(0, vocab_size, (b, s + 1)).astype(np.int32)
    return toks[:, :-1], toks[:, 1:]
