"""Seeded traffic: arrival times, tenant popularity and request mixes.

Every draw is stratified and then shuffled by the seed, so two seeds give
the same multiset of gaps, tenant ranks and configurations in another
order.  The window's amount of work is then the same on every seed, and
seeds differ only in how it is arranged.
"""

from __future__ import annotations

import itertools
from typing import Dict, List, Sequence

import numpy as np


def rng(seed: int, stream: str) -> np.random.Generator:
    """An independent generator per (seed, stream)."""
    return np.random.default_rng([int(seed) & 0xFFFFFFFFFFFFFFFF,
                                  int.from_bytes(stream.encode(), "little")])


def _strata(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def poisson_arrivals(rate: float, seconds: float, seed: int) -> np.ndarray:
    """Offsets in [0, seconds) of ``round(rate * seconds)`` arrivals whose
    gaps are the exponential distribution's quantiles in seeded order."""
    n = int(round(rate * seconds))
    if n <= 0:
        return np.zeros(0)
    gaps = -np.log1p(-_strata(n)) / rate
    gaps = rng(seed, "gaps").permutation(gaps) * (seconds / gaps.sum())
    return np.concatenate([[0.0], np.cumsum(gaps)[:-1]])


def zipf_ranks(n: int, n_items: int, s: float, seed: int) -> np.ndarray:
    """``n`` item ranks (0 = most popular) under Zipf(``s``) over
    ``n_items``: the inverse CDF at stratified points, in seeded order."""
    pmf = 1.0 / np.arange(1, n_items + 1) ** s
    cdf = np.cumsum(pmf / pmf.sum())
    ranks = np.minimum(np.searchsorted(cdf, _strata(n)), n_items - 1)
    return rng(seed, "zipf").permutation(ranks)


def uniform_indices(n: int, n_items: int, seed: int, stream: str) -> np.ndarray:
    """``n`` indices spread evenly over ``n_items``, in seeded order."""
    return rng(seed, stream).permutation(np.arange(n) * n_items // max(n, 1))


def mix(n: int, shares: Dict[str, float], seed: int) -> List[str]:
    """``n`` request kinds with exactly ``round(share * n)`` of each but
    the last kind, which takes the rest, in seeded order."""
    kinds: List[str] = []
    names = list(shares)
    for name in names[:-1]:
        kinds += [name] * int(round(shares[name] * n))
    kinds += [names[-1]] * (n - len(kinds))
    return [kinds[i] for i in rng(seed, "mix").permutation(n)]


def tenant_contexts(axes: Dict[str, Sequence[float]], n_window: int,
                    n_warmup: int, seed: int) -> tuple:
    """Distinct contexts from the product of ``axes``: ``n_window`` window
    tenants, most popular first, and ``n_warmup`` others for the warm-up."""
    names = list(axes)
    grid = list(itertools.product(*(axes[k] for k in names)))
    if n_window + n_warmup > len(grid):
        raise ValueError(f"{len(grid)} contexts cannot hold {n_window} window "
                         f"and {n_warmup} warm-up tenants")
    order = rng(seed, "tenants").permutation(len(grid))
    ctx = [dict(zip(names, map(float, grid[i]))) for i in order]
    return ctx[:n_window], ctx[n_window:n_window + n_warmup]


def log_uniform_contexts(ranges: Dict[str, Sequence[float]], n: int,
                         seed: int) -> List[dict]:
    """``n`` contexts, each value log-uniform in its [low, high]."""
    r = rng(seed, "contexts")
    cols = {k: np.exp(r.uniform(np.log(lo), np.log(hi), n))
            for k, (lo, hi) in ranges.items()}
    return [{k: float(round(cols[k][i], 3)) for k in ranges} for i in range(n)]


def grid_candidate(knobs: Dict[str, Sequence], i: int) -> dict:
    """The ``i``-th candidate of the product of ``knobs`` (first knob
    slowest), with the values as written."""
    names = list(knobs)
    idx = np.unravel_index(int(i), [len(knobs[k]) for k in names])
    return {k: knobs[k][j] for k, j in zip(names, idx)}
