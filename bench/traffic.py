"""The traffic generator.  It reads only the parameters of its traffic
file and the seed, so a new mix is a new data file.

``TrainFeed`` copies ``repro.data.SyntheticLM.batch``: Zipf unigram
tokens with one copied span per row, next-token labels, keyed by
(seed, step).  Every seed gets the same batch shape and step count, so
a seed changes which tokens are trained on and never how much work
there is.
"""

from __future__ import annotations

import numpy as np


class TrainFeed:
    """``batch(step)`` -> (tokens, labels), both (batch, seq) int32."""

    def __init__(self, traffic: dict, vocab: int, seed: int) -> None:
        self.batch_size = int(traffic["batch"])
        self.seq = int(traffic["seq"])
        self.copy_frac = float(traffic["copy_frac"])
        self.seed = int(seed)
        w = np.arange(1, vocab + 1, dtype=np.float64) ** -float(traffic["zipf_a"])
        self._cdf = np.cumsum(w / w.sum())
        self._cdf[-1] = 1.0

    @property
    def tokens_per_step(self) -> int:
        return self.batch_size * self.seq

    def batch(self, step: int) -> tuple[np.ndarray, np.ndarray]:
        rng = np.random.default_rng(np.random.SeedSequence([self.seed, 0, step]))
        B, S = self.batch_size, self.seq
        toks = np.searchsorted(self._cdf, rng.random((B, S + 1))).astype(np.int32)
        span = max(4, int(S * self.copy_frac) // 2)
        if span * 2 < S:
            start = rng.integers(0, S - 2 * span, size=B)
            for b in range(B):
                s = start[b]
                toks[b, s + span : s + 2 * span] = toks[b, s : s + span]
        return toks[:, :-1], toks[:, 1:]
