"""Seeded training observations for the predictors the benchmark serves.

Each row is one candidate of a knob grid measured in one workload context.
The target follows the repository's knob sweep (workers and prefetch help
with diminishing returns, larger batches amortise overhead) scaled by the
context: faster storage and larger files read faster, and the block size
that suits a file grows with it.  So a fitted ensemble splits on context
features as well as knobs, and tenants with different contexts get
different scores and rankings.

Every seed draws the same multiset of contexts (stratified log-uniform
points of each axis) in another order, and the same jitter values in
another order, so the fit does the same work on every seed.
"""

from __future__ import annotations

from typing import Dict, List, Sequence

import numpy as np

from chipbench.schedule import grid_candidate, rng

TARGET = "target_throughput"


def _log_strata(lo: float, hi: float, n: int, r: np.random.Generator) -> np.ndarray:
    u = (np.arange(n) + 0.5) / n
    return r.permutation(np.exp(np.log(lo) + u * (np.log(hi) - np.log(lo))))


def throughput(cand: dict, ctx: Dict[str, float]) -> float:
    """Noise-free MB/s of one candidate in one context."""
    w = float(cand.get("num_workers", 0))
    pf = float(cand.get("prefetch_depth", 1))
    b = float(cand.get("batch_size", 64))
    blk = float(cand.get("block_kb", 64))
    thr = 80.0 * (1 + 0.9 * w ** 0.7) * (1 + 0.15 * (pf - 1)) * (b / 64.0) ** 0.2
    fs = ctx.get("file_size_mb", 64.0)
    thr *= (ctx.get("throughput_mb_s", 500.0) / 500.0) ** 0.35
    thr *= (fs / 64.0) ** 0.15 * (ctx.get("iops", 20000.0) / 20000.0) ** 0.1
    thr *= (ctx.get("n_samples", 1000.0) / 1000.0) ** -0.05
    # the best block is about 16 KB per MB of file, within the grid's range
    best = np.log2(np.clip(fs * 16.0, 4.0, 4096.0))
    thr *= 1.0 + 0.25 * np.exp(-((np.log2(blk) - best) ** 2) / 8.0)
    thr *= 1.0 + 0.03 * (float(cand.get("n_threads", 1)) ** 0.5 - 1.0) / (1 + w)
    return float(thr)


def observations(knobs: Dict[str, Sequence], axes: Dict[str, Sequence[float]],
                 repeats: int, seed: int) -> List[dict]:
    """``repeats`` passes over the product of ``knobs``, each row in its own
    context drawn from ``axes`` ({name: [low, high]}), with 1% jitter."""
    n_cand = int(np.prod([len(v) for v in knobs.values()]))
    n = repeats * n_cand
    ctx_cols = {k: _log_strata(lo, hi, n, rng(seed, f"obs/{k}"))
                for k, (lo, hi) in axes.items()}
    jitter = rng(seed, "obs/jitter").permutation(np.linspace(-0.01, 0.01, n))
    rows = []
    for i in range(n):
        cand = grid_candidate(knobs, i % n_cand)
        ctx = {k: float(c[i]) for k, c in ctx_cols.items()}
        rows.append({**cand, **ctx, TARGET: throughput(cand, ctx) * (1 + jitter[i])})
    return rows
