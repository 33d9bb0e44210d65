"""Shared dense-ensemble representation + JAX inference for GBT and RF.

An ensemble of B trees, each padded to ``max_nodes``, is stored as stacked
arrays ``[B, max_nodes]``.  Prediction descends all trees in lockstep for
``max_depth+1`` gather steps — a dense, branch-free tensor program that jit's,
vmaps and shards cleanly (and backs the Pallas kernel in
``repro/kernels/gbt_predict.py``).
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from .tree import TreeArrays

__all__ = [
    "PackedEnsemble",
    "pack_trees",
    "predict_ensemble",
    "predict_ensemble_np",
    "ceil_pow2",
]


def ceil_pow2(n: int, floor: int = 1) -> int:
    """Smallest power of two >= max(n, floor).

    The serving tier's micro-batcher pads row counts to powers of two, so
    the number of distinct jit-compiled shapes stays logarithmic in the
    batch-size range."""
    return 1 << max(max(int(n), int(floor)) - 1, 0).bit_length()


@dataclasses.dataclass
class PackedEnsemble:
    feature: jnp.ndarray  # int32  [B, N]
    threshold: jnp.ndarray  # float32[B, N]
    left: jnp.ndarray  # int32  [B, N]
    right: jnp.ndarray  # int32  [B, N]
    value: jnp.ndarray  # float32[B, N]
    max_depth: int
    base_score: float = 0.0
    scale: float = 1.0  # learning rate (GBT) or 1/B (RF), folded at predict

    @property
    def n_trees(self) -> int:
        return int(self.feature.shape[0])

    def tree_dict(self):
        return dict(
            feature=self.feature,
            threshold=self.threshold,
            left=self.left,
            right=self.right,
            value=self.value,
        )


def pack_trees(
    trees: Sequence[TreeArrays], max_depth: int, base_score: float, scale: float
) -> PackedEnsemble:
    """Stack trees into [B, max_nodes] arrays, padding in place.

    One flat scatter per field instead of 5 slice-assignments per tree: the
    batched engine fits a 100-tree paper forest in tens of milliseconds, at
    which point 500 small ``__setitem__`` calls are a visible fraction of the
    whole fit.  Padded slots are self-looping zero-value leaves.
    """
    B = len(trees)
    ks = np.asarray([t.n_nodes for t in trees], np.int64)
    N = int(ks.max())
    # flat positions of every real node: tree b's node i at b*N + i
    starts = np.concatenate([[0], np.cumsum(ks)[:-1]])
    pos = np.repeat(np.arange(B, dtype=np.int64) * N, ks) + (
        np.arange(int(ks.sum())) - np.repeat(starts, ks)
    )

    def scat(field, fill, dtype):
        buf = np.full(B * N, fill, dtype) if np.isscalar(fill) else fill
        buf[pos] = np.concatenate([getattr(t, field) for t in trees])
        return buf.reshape(B, N)

    # Padded nodes self-loop so the fixed-depth descent stays put on them.
    feature = scat("feature", -1, np.int32)
    threshold = scat("threshold", 0.0, np.float32)
    value = scat("value", 0.0, np.float32)
    left = scat("left", np.tile(np.arange(N, dtype=np.int32), B), np.int32)
    right = scat("right", np.tile(np.arange(N, dtype=np.int32), B), np.int32)
    return PackedEnsemble(
        feature=jnp.asarray(feature),
        threshold=jnp.asarray(threshold),
        left=jnp.asarray(left),
        right=jnp.asarray(right),
        value=jnp.asarray(value),
        max_depth=max_depth,
        base_score=base_score,
        scale=scale,
    )


def _descend_one_tree(feature, threshold, left, right, value, x, max_depth):
    """Descend one tree for one row. x: [D]."""

    def step(_, idx):
        f = feature[idx]
        leaf = f < 0
        fx = x[jnp.maximum(f, 0)]
        nxt = jnp.where(fx <= threshold[idx], left[idx], right[idx])
        return jnp.where(leaf, idx, nxt)

    idx = jax.lax.fori_loop(0, max_depth + 1, step, jnp.int32(0))
    return value[idx]


@partial(jax.jit, static_argnames=("max_depth",))
def _predict_packed(tree_arrays: dict, X: jnp.ndarray, max_depth: int) -> jnp.ndarray:
    """Sum of per-tree predictions. X: [n, D] -> [n]."""
    per_tree = jax.vmap(  # over trees
        lambda f, t, l, r, v: jax.vmap(  # over rows
            lambda x: _descend_one_tree(f, t, l, r, v, x, max_depth)
        )(X)
    )(
        tree_arrays["feature"],
        tree_arrays["threshold"],
        tree_arrays["left"],
        tree_arrays["right"],
        tree_arrays["value"],
    )
    return per_tree.sum(axis=0)


def predict_ensemble(ens: PackedEnsemble, X: jnp.ndarray) -> jnp.ndarray:
    """base_score + scale * sum_b tree_b(X).  X: [n, D] float32."""
    X = jnp.asarray(X, jnp.float32)
    raw = _predict_packed(ens.tree_dict(), X, ens.max_depth)
    return ens.base_score + ens.scale * raw


def predict_ensemble_np(ens: PackedEnsemble, X: np.ndarray) -> np.ndarray:
    """Pure-numpy oracle, used in tests against the JAX/Pallas paths."""
    from .tree import TreeArrays, predict_tree_np

    total = np.zeros(X.shape[0], dtype=np.float64)
    for b in range(ens.n_trees):
        t = TreeArrays(
            feature=np.asarray(ens.feature[b]),
            threshold=np.asarray(ens.threshold[b]),
            left=np.asarray(ens.left[b]),
            right=np.asarray(ens.right[b]),
            value=np.asarray(ens.value[b]),
            gain=np.zeros_like(np.asarray(ens.value[b])),
            cover=np.zeros_like(np.asarray(ens.value[b])),
        )
        total += predict_tree_np(t, X, ens.max_depth)
    return ens.base_score + ens.scale * total
