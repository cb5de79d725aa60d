"""Traffic generation from `--seed`: series, queries and arrival times.

Everything a run feeds the system comes from here, keyed by the run's seed
and a purpose, so the same seed gives the same inputs and no two purposes
share a stream. Every seed gives the same sizes and the same arrival
schedule, so seeds change the data and not the amount of work.
"""

from __future__ import annotations

import numpy as np

# purposes, so that streams drawn for different things never coincide
WARM, SERIES, QUERY, CORPUS, SAMPLE, ARRIVALS = range(6)


def rng(seed: int, purpose: int, index: int = 0) -> np.random.Generator:
    """A generator for one purpose of one run. `seed` may exceed 32 bits."""
    return np.random.default_rng(
        np.random.SeedSequence([int(seed), purpose, index]))


def random_walk(n: int, gen: np.random.Generator) -> np.ndarray:
    """A random walk of `n` float32 samples: the synthetic series of the
    matrix-profile literature."""
    return np.cumsum(gen.normal(size=n)).astype(np.float32)


def walk(seed: int, purpose: int, index: int, n: int) -> np.ndarray:
    return random_walk(n, rng(seed, purpose, index))


def arrivals(rate: float, seconds: float) -> np.ndarray:
    """Due times in [0, seconds) of an open loop at `rate` per second.

    The gaps are the quantiles of an exponential distribution (Poisson
    arrivals), scaled to fill the window exactly, in one shuffled order
    that no seed changes. Near the knee the order alone decides the tail:
    on one v5e at 3.4 queries/s, seeded orders moved the p90 latency of a
    10 s window between 0.63 s and 1.88 s. So seeds change the queries and
    the corpus, and every seed offers the same schedule."""
    n = max(1, int(round(rate * seconds)))
    gaps = -np.log1p(-(np.arange(n) + 0.5) / n)
    gaps *= seconds / gaps.sum()
    gaps = gaps[rng(0, ARRIVALS).permutation(n)]
    return np.concatenate([[0.0], np.cumsum(gaps)[:-1]])


def sample(seed: int, index: int, population: int, size: int) -> np.ndarray:
    """`size` distinct indices of `population`, drawn from the seed."""
    gen = rng(seed, SAMPLE, index)
    return np.sort(gen.choice(population, size=min(size, population),
                              replace=False))
