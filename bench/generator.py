"""The one generator of inputs: reads a traffic file's parameters.

A training mix names its sequence length and the token distribution.
Each step's batch is drawn from ``SeedSequence([seed, step])``, so the
same seed gives the same batches and the rows of different steps differ.
The Zipf draw is the benchmark's own copy of the program's synthetic
stream (``repro.data.synthetic.TokenStream``): p(rank r) ∝ r^-s.
"""
from __future__ import annotations

from typing import Mapping

import numpy as np


def _zipf_probs(vocab: int, s: float) -> np.ndarray:
    p = np.arange(1, vocab + 1, dtype=np.float64) ** -float(s)
    return p / p.sum()


def token_batches(traffic: Mapping, vocab: int, rows: int, seed: int,
                  steps: range) -> np.ndarray:
    """int32 [len(steps), rows, seq_len] token ids, one batch per step."""
    if traffic["kind"] != "train" or traffic["tokens"] != "zipf":
        raise ValueError(f"unsupported traffic {dict(traffic)}")
    p = _zipf_probs(vocab, traffic["zipf_exponent"])
    seq = traffic["seq_len"]
    out = np.empty((len(steps), rows, seq), np.int32)
    for i, step in enumerate(steps):
        rng = np.random.default_rng(np.random.SeedSequence([seed, step]))
        out[i] = rng.choice(vocab, size=(rows, seq), p=p)
    return out
