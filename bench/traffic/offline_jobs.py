"""Offline batch jobs: every request of a job is there at once.

A mix file gives the prompt and output length distributions, each a
lognormal (``median``, ``sigma``) clipped to [``min``, ``max``]; other keys
(its source, its cuts) are notes for the reader. Every job holds the same
requests' sizes in the same order: the distributions' quantiles at
(i + 0.5) / n, paired and ordered by one fixed shuffle. The order is part of
the work: a job runs more requests than the engine has slots, and which
requests share the last rounds sets how long the job drains. So every seed
does the same work, and the seed draws the token ids, uniform over the
vocabulary.
"""
from __future__ import annotations

import dataclasses
from statistics import NormalDist

import numpy as np


@dataclasses.dataclass(frozen=True)
class Request:
    uid: int
    prompt: np.ndarray      # int32 [L]
    max_new: int


def lengths(dist: dict, n: int) -> np.ndarray:
    """The n quantile lengths of a clipped lognormal, ascending."""
    z = np.array([NormalDist().inv_cdf((i + 0.5) / n) for i in range(n)])
    v = np.round(dist["median"] * np.exp(dist["sigma"] * z))
    return np.clip(v, dist["min"], dist["max"]).astype(np.int64)


class Traffic:
    def __init__(self, mix: dict, *, job_requests: int, vocab: int,
                 seed: int):
        self.n = int(job_requests)
        self.vocab = int(vocab)
        self.seed = int(seed)
        # pairing and order are part of the work: the same for every seed
        shuffle = np.random.default_rng(0)
        self.prompt_lens = lengths(mix["prompt"], self.n)[
            shuffle.permutation(self.n)]
        self.output_lens = lengths(mix["output"], self.n)[
            shuffle.permutation(self.n)]

    def job(self, j: int) -> list[Request]:
        """The requests of job ``j``, uids unique across jobs."""
        rng = np.random.default_rng([self.seed, j])
        return [Request(uid=j * self.n + i + 1,
                        prompt=rng.integers(0, self.vocab,
                                            int(self.prompt_lens[i]),
                                            dtype=np.int32),
                        max_new=int(self.output_lens[i]))
                for i in range(self.n)]


def make(mix: dict, *, job_requests: int, vocab: int, seed: int) -> Traffic:
    """The generator's entry, as the harness calls every generator."""
    return Traffic(mix, job_requests=job_requests, vocab=vocab, seed=seed)
