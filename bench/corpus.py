"""The benchmark's token corpus: a copy of the program's ``SyntheticLM``.

Kept here, not imported, so that a change to the program's generator cannot
move the yardstick.  It departs from the program's in one point: the
k-gram table is drawn again while it permutes the ids.  Each sample mixes
a deterministic k-gram source with uniform noise tokens; the noise
fraction is the sample's difficulty, so easy samples become confidently
correct early (candidates for hiding) and hard ones keep a high loss.  Everything follows from ``seed``, and each row
from its own per-sample seed, so ``get`` is per-index deterministic.
"""
from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass
class SyntheticLM:
    num_samples: int
    seq_len: int
    vocab_size: int
    easy_fraction: float
    order: int
    seed: int

    def __post_init__(self):
        rng = np.random.default_rng(self.seed)
        n = self.num_samples
        # A table that permutes the ids has a uniform unigram, which a
        # random-init model can fail to learn at all (a source stepping
        # through one cycle of 4 ids stayed at loss log 4 for 8 epochs on 1
        # seed of 6 on the chip): draw again until some id repeats.
        while True:
            self.table = rng.integers(
                0, self.vocab_size,
                (self.vocab_size,) * self.order).astype(np.int32)
            if len(np.unique(self.table)) < self.table.size:
                break
        easy = rng.random(n) < self.easy_fraction
        self.difficulty = np.where(
            easy, rng.uniform(0.0, 0.15, n), rng.uniform(0.4, 0.9, n)
        ).astype(np.float32)
        self.sample_seed = rng.integers(0, 2**31, n)

    def _gen_one(self, idx: int) -> np.ndarray:
        r = np.random.default_rng(int(self.sample_seed[idx]))
        s = self.seq_len + 1
        seq = np.empty(s, np.int32)
        seq[: self.order] = r.integers(0, self.vocab_size, self.order)
        noise = r.random(s) < self.difficulty[idx]
        for t in range(self.order, s):
            if noise[t]:
                seq[t] = r.integers(0, self.vocab_size)
            else:
                seq[t] = self.table[tuple(seq[t - self.order : t])]
        return seq

    def arrays(self) -> dict:
        return self.get(np.arange(self.num_samples))

    def get(self, indices: np.ndarray) -> dict:
        seqs = np.stack([self._gen_one(int(i)) for i in indices])
        return {
            "tokens": seqs[:, :-1],
            "labels": seqs[:, 1:].astype(np.int32),
            "mask": np.ones((len(indices), self.seq_len), bool),
        }
