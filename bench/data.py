"""Token batches from the seed: Zipf unigrams with copy structure.

A copy of the program's synthetic generator (``repro.data.synthetic.
make_batch``), kept here so that what the benchmark feeds cannot change
under it.  Batch ``i`` of seed ``s`` depends on ``(s, i)`` alone, so the
program and the reference see the same rows.
"""

from __future__ import annotations

from typing import Any, Dict

import numpy as np


def _zipf_probs(vocab: int, alpha: float) -> np.ndarray:
    p = np.arange(1, vocab + 1, dtype=np.float64) ** -alpha
    return p / p.sum()


def make_tokens(traffic: Dict[str, Any], vocab: int, seed: int,
                index: int) -> np.ndarray:
    """(global_batch, seq_len) int32 tokens of batch ``index``."""
    b, s = int(traffic["global_batch"]), int(traffic["seq_len"])
    lag = int(traffic["repeat_lag"])
    rng = np.random.default_rng(np.random.SeedSequence([seed, index]))
    toks = rng.choice(vocab, size=(b, s),
                      p=_zipf_probs(vocab, float(traffic["zipf_alpha"])))
    # copy structure: with prob repeat_prob, token t repeats token t - lag
    mask = rng.random((b, s)) < float(traffic["repeat_prob"])
    mask[:, :lag] = False
    return np.where(mask, np.roll(toks, lag, axis=1), toks).astype(np.int32)


def pool(traffic: Dict[str, Any], vocab: int, seed: int) -> list:
    """The cell's pool of distinct batches, cycled through by the window."""
    return [make_tokens(traffic, vocab, seed, i)
            for i in range(int(traffic["pool"]))]
