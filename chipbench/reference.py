"""Plain reference of the scoring semantics, in numpy, and its control.

A fitted tree ensemble is the deployment's data: per tree, node arrays
``feature`` (-1 at a leaf), ``threshold``, ``left``, ``right`` and ``value``.
A row goes left where ``x[feature] <= threshold``; its score is
``base + scale * sum of the leaf values it reaches``.  The reference walks
every tree in float64 and shares no code with the program: not its
featurisation, grid assembly, descent, kernel tables, top-k or cache.

``precision="bfloat16"`` is the control: the same walk with features,
thresholds, leaf values and the running sum rounded to bfloat16, the step
below the float32 the device paths compute in.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Sequence

import ml_dtypes
import numpy as np

from chipbench.schedule import grid_candidate

BF16 = ml_dtypes.bfloat16


@dataclasses.dataclass
class Ensemble:
    feature: np.ndarray    # int   [trees, nodes]
    threshold: np.ndarray  # float [trees, nodes]
    left: np.ndarray
    right: np.ndarray
    value: np.ndarray
    max_depth: int
    base: float
    scale: float

    @classmethod
    def of(cls, packed) -> "Ensemble":
        """Copy the node arrays of a fitted ensemble to host numpy."""
        return cls(*(np.asarray(getattr(packed, k)) for k in
                     ("feature", "threshold", "left", "right", "value")),
                   max_depth=int(packed.max_depth), base=float(packed.base_score),
                   scale=float(packed.scale))

    def used_features(self) -> List[int]:
        return sorted({int(f) for f in np.unique(self.feature) if f >= 0})

    def real_nodes(self) -> int:
        """Nodes reachable from each root, summed over trees."""
        n = 0
        for b in range(self.feature.shape[0]):
            frontier, seen = [0], set()
            while frontier:
                i = frontier.pop()
                if i in seen:
                    continue
                seen.add(i)
                if self.feature[b, i] >= 0:
                    frontier += [int(self.left[b, i]), int(self.right[b, i])]
            n += len(seen)
        return n


def scores(ens: Ensemble, X: np.ndarray, precision: str = "float64") -> np.ndarray:
    """Log-space scores of the rows of ``X`` [rows, features], as float64."""
    if precision == "float64":
        X = np.asarray(X, np.float64)
        thr = ens.threshold.astype(np.float64)
        val = ens.value.astype(np.float64)
        total = np.zeros(X.shape[0], np.float64)
    elif precision == "bfloat16":
        X = np.asarray(X, np.float64).astype(BF16).astype(np.float32)
        thr = ens.threshold.astype(BF16).astype(np.float32)
        val = ens.value.astype(BF16)
        total = np.zeros(X.shape[0], BF16)
    else:
        raise ValueError(f"unknown precision {precision!r}")
    rows = np.arange(X.shape[0])
    for b in range(ens.feature.shape[0]):
        idx = np.zeros(X.shape[0], np.int64)
        for _ in range(ens.max_depth):
            f = ens.feature[b, idx]
            internal = f >= 0
            go_left = X[rows, np.maximum(f, 0)] <= thr[b, idx]
            nxt = np.where(go_left, ens.left[b, idx], ens.right[b, idx])
            idx = np.where(internal, nxt, idx)
        if precision == "float64":
            total += val[b, idx]
        else:
            total = (total.astype(np.float32) + val[b, idx].astype(np.float32)).astype(BF16)
    if precision == "float64":
        return ens.base + ens.scale * total
    base, scale = np.float32(BF16(ens.base)), np.float32(BF16(ens.scale))
    return np.asarray((base + (scale * total.astype(np.float32)).astype(BF16)
                       .astype(np.float32)).astype(BF16), np.float64)


def row(names: Sequence[str], values: Dict[str, float]) -> np.ndarray:
    """One feature row: each named feature from ``values``, 0 where absent."""
    return np.asarray([float(values.get(n, 0.0)) for n in names], np.float64)


def _sub_grid(ens: Ensemble, names: Sequence[str], knobs: Dict[str, Sequence],
              context: Dict[str, float]):
    """The feature rows of the product of the knobs a tree splits on (the
    others held at their first value), and that product's shape."""
    knob_names = list(knobs)
    used = {names[f] for f in ens.used_features()}
    dims = [len(knobs[k]) if k in used and k in names else 1 for k in knob_names]
    sub = np.stack(np.meshgrid(*[np.arange(d) for d in dims], indexing="ij"),
                   -1).reshape(-1, len(dims))
    X = np.empty((sub.shape[0], len(names)), np.float64)
    for j, n in enumerate(names):
        if n in knobs:
            k = knob_names.index(n)
            vals = np.asarray(knobs[n], np.float64)
            X[:, j] = vals[sub[:, k]] if dims[k] > 1 else vals[0]
        else:
            X[:, j] = float(context.get(n, 0.0))
    return X, dims


def _broadcast(sub_scores: np.ndarray, dims: List[int], knobs: Dict[str, Sequence]):
    full = [len(v) for v in knobs.values()]
    return np.broadcast_to(sub_scores.reshape(dims), full).reshape(-1)


def grid_scores(ens: Ensemble, names: Sequence[str], knobs: Dict[str, Sequence],
                context: Dict[str, float], precision: str = "float64") -> np.ndarray:
    """Scores of every candidate of the product of ``knobs`` (first knob
    slowest) in ``context``.  A knob no tree splits on cannot change a
    score, so the walk runs over the used knobs' product and the result is
    broadcast along the others."""
    X, dims = _sub_grid(ens, names, knobs, context)
    return _broadcast(scores(ens, X, precision), dims, knobs)


class Grid:
    """The reference over one candidate grid (the product of ``knobs``,
    first knob slowest) for the feature row ``names``."""

    def __init__(self, ens: Ensemble, names: Sequence[str], knobs: Dict[str, Sequence]):
        self.ens, self.names, self.knobs = ens, list(names), knobs
        used = {self.names[f] for f in ens.used_features()}
        self._ctx_used = [n for n in self.names if n in used and n not in knobs]
        self._memo: Dict[tuple, np.ndarray] = {}

    def _key(self, context: Dict[str, float], precision: str) -> tuple:
        # contexts that agree on every feature a tree splits on score alike
        return (precision,) + tuple(float(context.get(n, 0.0)) for n in self._ctx_used)

    def scores(self, context: Dict[str, float], precision: str = "float64") -> np.ndarray:
        self.walk([context], precision)
        return self._memo[self._key(context, precision)]

    def walk(self, contexts: Sequence[Dict[str, float]], precision: str = "float64") -> None:
        """Score the grid in every context not scored yet, in one walk."""
        todo = {}
        for c in contexts:
            todo.setdefault(self._key(c, precision), c)
        todo = {k: c for k, c in todo.items() if k not in self._memo}
        if not todo:
            return
        blocks = [_sub_grid(self.ens, self.names, self.knobs, c) for c in todo.values()]
        dims = blocks[0][1]
        out = scores(self.ens, np.concatenate([X for X, _ in blocks]), precision)
        for i, key in enumerate(todo):
            part = out[i * blocks[0][0].shape[0]:(i + 1) * blocks[0][0].shape[0]]
            self._memo[key] = _broadcast(part, dims, self.knobs)

    def index(self, pick: dict) -> int:
        """The grid index of a served candidate, -1 when it is not in the grid."""
        try:
            pos = [list(self.knobs[k]).index(pick[k]) for k in self.knobs]
        except (KeyError, ValueError):
            return -1
        return int(np.ravel_multi_index(pos, [len(v) for v in self.knobs.values()]))

    def readings(self, context: Dict[str, float], top: List[dict], k: int) -> Dict[str, float]:
        """``topk_readings`` of a served top-``k`` list of candidate dicts."""
        return topk_readings(self.scores(context), [self.index(t) for t in top],
                             [t.get("predicted_throughput_mb_s", np.nan) for t in top], k)

    def control_top(self, context: Dict[str, float], k: int) -> List[dict]:
        """The top-``k`` the bfloat16 control answers with."""
        s = self.scores(context, "bfloat16")
        return [{**grid_candidate(self.knobs, int(i)),
                 "predicted_throughput_mb_s": float(np.expm1(s[i]))}
                for i in np.argsort(-s, kind="stable")[:k]]


def topk_readings(ref_scores: np.ndarray, picks: Sequence[int],
                  pick_values: Sequence[float], k: int) -> Dict[str, float]:
    """How far a served top-``k`` lies from the reference's.

    ``gap``: the largest amount, in log space, by which the reference score
    of the i-th best pick falls below the reference's i-th best score (0
    when the picks are a top ``k``, ties included; the whole log range when
    fewer than ``k`` distinct grid candidates were picked).  ``rel_err``: the largest
    relative error of a reported MB/s value against ``expm1`` of its pick's
    reference score (1 when the picks are malformed)."""
    best = np.sort(ref_scores)[::-1][:k]
    picks = list(picks)
    if len(picks) != k or len(set(picks)) != k or min(picks) < 0:
        return {"gap": float(best[0] - np.min(ref_scores)), "rel_err": 1.0}
    got = np.sort(ref_scores[picks])[::-1]
    want_vals = np.expm1(ref_scores[picks])
    rel = np.abs(np.asarray(pick_values, np.float64) - want_vals) / np.abs(want_vals)
    return {"gap": float(np.max(best - got)), "rel_err": float(np.max(rel))}
