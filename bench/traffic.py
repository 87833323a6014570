"""The one traffic generator: a training job's batches from its mix file.

A mix (``bench/traffic/<name>.json``) fixes the batch, the sequence length
and the token statistics; every batch is a pure function of (seed, step),
so two runs with one seed feed the same rows and every step's rows differ.
The statistics are those of ``repro.data.SyntheticLMDataset`` (copied here so
that the benchmark's inputs do not move with the program): Zipf(1.1) token
ids over the vocabulary without id 0, id 0 starting a document with the
mix's rate, and no prediction across a document start.
"""
from __future__ import annotations

import numpy as np

IGNORE = -100
PAD_ID = 0


class Batches:
    def __init__(self, mix: dict, vocab_size: int, seed: int):
        self.batch_size = int(mix["batch"])
        self.seq = int(mix["seq"])
        self.doc_start_rate = float(mix["doc_start_rate"])
        self.seed = int(seed)
        ranks = np.arange(1, vocab_size, dtype=np.float64)
        probs = 1.0 / ranks ** float(mix["zipf"])
        self._cdf = np.cumsum(probs / probs.sum())
        self._cdf[-1] = 1.0

    @property
    def tokens_per_batch(self) -> int:
        return self.batch_size * self.seq

    def batch(self, step: int) -> dict:
        """``{"tokens", "labels"}``, int32 ``(batch, seq)``, for ``step``."""
        rng = np.random.default_rng(np.random.SeedSequence([self.seed, step]))
        shape = (self.batch_size, self.seq + 1)
        toks = np.searchsorted(self._cdf, rng.random(shape), side="right") + 1
        starts = rng.random(shape) < self.doc_start_rate
        toks = np.where(starts, PAD_ID, toks).astype(np.int32)
        tokens = toks[:, :-1]
        labels = np.where(tokens == PAD_ID, IGNORE, toks[:, 1:]).astype(np.int32)
        return {"tokens": np.ascontiguousarray(tokens), "labels": labels}
