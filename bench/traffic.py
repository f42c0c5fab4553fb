"""The benchmark's own copy of the synthetic token stream.

A copy of `SyntheticLM.batch_at` (src/repro/data/pipeline.py): the batch
at step k is a pure function of (seed, k). The program's pipeline is the
input layer under test and feeds the timed steps; this copy is the
yardstick. It feeds the reference, and the harness checks that the
program's batches equal it, token for token.

A traffic file (bench/workloads/<cell>.json, key `traffic`) gives the
parameters: sequence length, sequences per chip, the unigram skew
`zipf_a` and the share `markov_mix` of tokens that follow the previous one
by (t * 7 + 3) mod V.
"""
from __future__ import annotations

import numpy as np


class Stream:
    def __init__(self, vocab: int, seq_len: int, batch: int, seed: int,
                 zipf_a: float, markov_mix: float):
        self.vocab, self.seq_len, self.batch = vocab, seq_len, batch
        self.seed, self.zipf_a, self.markov_mix = seed, zipf_a, markov_mix
        probs = np.arange(1, vocab + 1, dtype=np.float64) ** -zipf_a
        self._probs = probs / probs.sum()

    def batch_at(self, step: int) -> dict:
        rng = np.random.default_rng(np.random.SeedSequence([self.seed, step]))
        B, T = self.batch, self.seq_len
        toks = rng.choice(self.vocab, size=(B, T + 1), p=self._probs)
        take = rng.random((B, T)) < self.markov_mix
        for t in range(T):
            follow = (toks[:, t] * 7 + 3) % self.vocab
            toks[:, t + 1] = np.where(take[:, t], follow, toks[:, t + 1])
        return {"tokens": toks[:, :-1].astype(np.int32),
                "labels": toks[:, 1:].astype(np.int32)}
