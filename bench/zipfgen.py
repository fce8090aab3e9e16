"""Bounded-domain Zipf sampling, kept with the benchmark.

A copy of the program's ``data/zipf.py`` arithmetic (inverse-CDF sampling
over the ranked domain, optional seeded rank->key permutation) so that a
change to the program cannot move the yardstick.  ``ZipfSampler`` keeps
the CDF of one (domain, alpha) pair, which the traffic generator reuses
for thousands of appends.
"""
from __future__ import annotations

import numpy as np


def zipf_pmf(domain: int, alpha: float) -> np.ndarray:
    ranks = np.arange(1, domain + 1, dtype=np.float64)
    w = ranks ** (-alpha) if alpha > 0 else np.ones_like(ranks)
    return w / w.sum()


class ZipfSampler:
    """Ranks in ``[0, domain)`` with Zipf(alpha) popularity (rank 0 is
    the most popular; alpha 0 is uniform)."""

    def __init__(self, domain: int, alpha: float):
        self.domain = int(domain)
        self.alpha = float(alpha)
        self.cdf = np.cumsum(zipf_pmf(self.domain, self.alpha))

    def ranks(self, rng: np.random.Generator, n: int) -> np.ndarray:
        r = np.searchsorted(self.cdf, rng.random(n), side="right")
        return np.minimum(r, self.domain - 1)


def zipf_keys(n: int, domain: int, alpha: float, seed: int = 0,
              permute: bool = True) -> np.ndarray:
    """``n`` int64 keys in ``[0, domain)``: the program's ``zipf_keys``."""
    rng = np.random.default_rng(seed)
    ranks = ZipfSampler(domain, alpha).ranks(rng, n)
    if permute:
        return rng.permutation(domain)[ranks].astype(np.int64)
    return ranks.astype(np.int64)


def apportion(pmf: np.ndarray, n: int) -> np.ndarray:
    """Integer counts summing to ``n`` in proportion to ``pmf`` (largest
    remainder): the same multiset for every seed."""
    exact = pmf * n
    counts = np.floor(exact).astype(np.int64)
    short = n - int(counts.sum())
    if short:
        counts[np.argsort(-(exact - counts), kind="stable")[:short]] += 1
    return counts
