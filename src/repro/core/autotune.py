"""Configuration recommendation + online pipeline autotuning (paper §5.2).

Two layers:

1. ``recommend()`` — the paper's offline use-case: enumerate a candidate grid
   of pipeline knobs, featurize each candidate, predict log-throughput with a
   fitted ``IOPerformancePredictor``, return ranked configs.  Small grids are
   ONE batched JAX ensemble inference (milliseconds for 10^5 candidates) over
   a cached feature matrix — per ``decide()`` only the scalar context columns
   are rewritten in place (zero per-candidate Python work).  Mega grids
   (``MEGA_GRID_MIN``+ candidates) with a GBT/RF predictor are scored in
   fixed-size float32 chunks through the packed-ensemble program — the Pallas
   one-hot-matmul kernel on TPU, the jitted dense descent elsewhere — so the
   per-tree intermediates stay VMEM/cache-resident instead of spilling
   O(n_candidates x n_trees) floats to DRAM; the classic numpy path remains
   the oracle (``scorer="oracle"``).

2. ``OnlineAutotuner`` — the framework integration: lives inside the trainer
   (step-granularity telemetry) or behind the ``repro.service`` loop/fleet
   (cycle-granularity campaign batches via ``ingest_records``), periodically
   refits, and proposes a reconfiguration whenever the predicted gain over the
   current config exceeds a threshold. This is the paper's "days -> minutes"
   loop run continuously, and doubles as straggler mitigation (a slow host
   re-tunes its own pipeline from its own telemetry).  Observations land in an
   incremental column store (amortized-doubling buffer), so a refit hands the
   model a zero-copy view of history instead of re-materializing every row.
"""

from __future__ import annotations

import dataclasses
import itertools
import threading
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

import numpy as np

from .ensemble_base import PackedEnsemble, ceil_pow2, predict_ensemble
from .features import AUTOTUNE_FEATURE_NAMES, FeatureSpec
from .predictor import IOPerformancePredictor, PredictorSnapshot

__all__ = [
    "ConfigSpace",
    "recommend",
    "score_grid",
    "OnlineAutotuner",
    "AutotuneDecision",
    "DEFAULT_SPACE",
    "MEGA_GRID_MIN",
    "MEGA_GRID_CHUNK",
]

KNOB_NAMES = ("batch_size", "num_workers", "block_kb", "n_threads", "prefetch_depth",
              "prefetch_policy", "lookahead_batches", "cache_budget_mb")


@dataclasses.dataclass(frozen=True)
class ConfigSpace:
    """Discrete grid over the tunable pipeline knobs (paper §3.1 parameters).

    The expanded grid (per-knob columns, candidate dicts, and per-spec feature
    matrices) is cached on the instance: ``OnlineAutotuner.decide`` calls
    ``recommend`` every step, and rebuilding 1,800+ row grids from dicts each
    time used to dominate the serving path.
    """

    batch_size: Sequence[int] = (16, 32, 64, 128, 256)
    num_workers: Sequence[int] = (0, 1, 2, 4, 8)
    block_kb: Sequence[int] = (4, 16, 64, 256, 1024, 4096)
    n_threads: Sequence[int] = (1, 2, 4, 8)
    prefetch_depth: Sequence[int] = (1, 2, 4)  # beyond-paper knob
    # prefetch-policy knobs (data/prefetch.py) — numeric policy codes
    # (0=off, 1=depth, 2=clairvoyant); single-valued by default so the
    # paper's 1,800-config grid is unchanged unless a campaign varies them
    prefetch_policy: Sequence[int] = (1,)
    lookahead_batches: Sequence[int] = (8,)
    cache_budget_mb: Sequence[float] = (64.0,)

    def __post_init__(self):
        for k in KNOB_NAMES:  # normalize to tuples (hashable, immutable)
            object.__setattr__(self, k, tuple(getattr(self, k)))
        object.__setattr__(self, "_cache", {})

    # -- grid expansion (cached) ---------------------------------------
    @property
    def n_candidates(self) -> int:
        n = 1
        for k in KNOB_NAMES:
            n *= len(getattr(self, k))
        return n

    def _grid_shape(self) -> Tuple[int, ...]:
        return tuple(len(getattr(self, k)) for k in KNOB_NAMES)

    def knob_columns(self) -> Dict[str, np.ndarray]:
        """Per-knob value columns of the expanded grid, in ``candidates()``
        order (itertools.product over KNOB_NAMES), each [n_candidates]."""
        cols = self._cache.get("knob_columns")
        if cols is None:
            grids = np.meshgrid(
                *[np.asarray(getattr(self, k), np.float64) for k in KNOB_NAMES],
                indexing="ij",
            )
            cols = {k: g.reshape(-1) for k, g in zip(KNOB_NAMES, grids)}
            self._cache["knob_columns"] = cols
        return cols

    def candidates(self) -> List[dict]:
        """Candidate knob dicts (cached; prefer ``candidate(i)`` / the column
        API for large grids — this materializes n_candidates dicts)."""
        cands = self._cache.get("candidates")
        if cands is None:
            grids = [getattr(self, k) for k in KNOB_NAMES]
            cands = [dict(zip(KNOB_NAMES, vals)) for vals in itertools.product(*grids)]
            self._cache["candidates"] = cands
        return cands

    def candidate(self, i: int) -> dict:
        """The i-th candidate dict (original Python value types), without
        materializing the whole list."""
        idx = np.unravel_index(int(i), self._grid_shape())
        return {k: getattr(self, k)[j] for k, j in zip(KNOB_NAMES, idx)}

    # -- zero-copy feature matrix --------------------------------------
    def feature_matrix(self, spec: FeatureSpec, context: dict) -> np.ndarray:
        """[n_candidates, n_features] matrix for ``spec``: knob columns from
        the cached grid, remaining features from scalar ``context`` values
        (missing -> 0.0, mirroring ``FeatureSpec.row``).

        The knob columns are written once and cached per spec; only the
        context columns are overwritten on subsequent calls.  The returned
        array is the cached buffer — treat it as read-only.
        """
        key = ("matrix", spec.names)
        X = self._cache.get(key)
        if X is None:
            X = spec.matrix_from_candidates(self.knob_columns(), self.n_candidates)
            self._cache[key] = X
        for k, name in enumerate(spec.names):
            if name not in KNOB_NAMES:
                X[:, k] = float(context.get(name, 0.0))
        return X


DEFAULT_SPACE = ConfigSpace()


# -- mega-grid scoring -----------------------------------------------------
# Above MEGA_GRID_MIN candidates, an ensemble-backed recommend() stops
# materializing the [n, F] float64 matrix + one monolithic inference and
# instead scores fixed-size float32 chunks assembled straight from the cached
# knob columns.  Chunks are MEGA_GRID_CHUNK rows; the tail is padded to a
# power of two (floor _MEGA_TAIL_FLOOR) so the jit cache stays logarithmic in
# the grid size, exactly like the serving tier's micro-batch buckets.
MEGA_GRID_MIN = 4096
MEGA_GRID_CHUNK = 8192
_MEGA_TAIL_FLOOR = 256


def _packed_model(predictor) -> Optional[PackedEnsemble]:
    """The predictor's ``PackedEnsemble`` when its ``predict`` is exactly the
    packed-ensemble program (GBT/RF models), else ``None``."""
    ens = getattr(getattr(predictor, "model", None), "ensemble", None)
    return ens if isinstance(ens, PackedEnsemble) else None


def _on_tpu() -> bool:
    import jax

    return jax.default_backend() == "tpu"


def _resolve_scorer(scorer: str, ens: Optional[PackedEnsemble], n: int) -> str:
    if scorer not in ("auto", "oracle", "chunked", "pallas"):
        raise ValueError(f"unknown scorer {scorer!r}")
    if scorer == "oracle" or ens is None:
        return "oracle"
    if scorer == "auto":
        if n < MEGA_GRID_MIN:
            return "oracle"
        return "pallas" if _on_tpu() else "chunked"
    return scorer


def _score_grid_packed(
    ens: PackedEnsemble,
    spec: FeatureSpec,
    space: ConfigSpace,
    context: dict,
    *,
    chunk: int,
    pallas: bool,
) -> np.ndarray:
    """Float32 log-space scores of every grid candidate, chunk by chunk.

    Each [chunk, F] block is written into a reused float32 buffer: knob
    columns sliced from the cached grid, context features (chunk-invariant)
    filled once per buffer shape.  Pad rows are scored and discarded — per-row
    descent is independent, so padding never changes a real row."""
    n = space.n_candidates
    cols = space.knob_columns()
    names = spec.names
    knob_cols = [(j, cols[name]) for j, name in enumerate(names) if name in KNOB_NAMES]
    ctx_vals = [
        (j, float(context.get(name, 0.0)))
        for j, name in enumerate(names)
        if name not in KNOB_NAMES
    ]
    if pallas:
        from ..kernels.ops import gbt_predict_op
    scores = np.empty(n, np.float32)
    buffers: Dict[int, np.ndarray] = {}
    lo = 0
    while lo < n:
        rows = min(chunk, n - lo)
        padded = chunk if rows == chunk else ceil_pow2(rows, _MEGA_TAIL_FLOOR)
        buf = buffers.get(padded)
        if buf is None:
            buf = np.zeros((padded, len(names)), np.float32)
            for j, v in ctx_vals:
                buf[:, j] = v
            buffers[padded] = buf
        for j, col in knob_cols:
            buf[:rows, j] = col[lo : lo + rows]
            if rows < padded:
                buf[rows:, j] = 0.0
        if pallas:
            out = gbt_predict_op(buf, ens)
        else:
            out = predict_ensemble(ens, buf)
        scores[lo : lo + rows] = np.asarray(out)[:rows]
        lo += rows
    return scores


def score_grid(
    predictor,
    context: dict,
    space: ConfigSpace = DEFAULT_SPACE,
    *,
    scorer: str = "auto",
    chunk: int = MEGA_GRID_CHUNK,
) -> Tuple[np.ndarray, str]:
    """Score every candidate in the grid; returns ``(scores, mode)``.

    ``scores`` is [n_candidates] and monotone in predicted throughput: raw
    MB/s float64 under ``"oracle"`` (the classic batched numpy path), float32
    log-space ensemble outputs under ``"chunked"``/``"pallas"`` (expm1 is
    monotone, so the ranking is the same and the mega path skips n expm1s).
    ``scorer="auto"`` picks the packed path for ensemble models on grids of
    ``MEGA_GRID_MIN``+ candidates — the Pallas kernel on TPU, the jitted
    dense descent elsewhere — and the oracle otherwise; forcing
    ``"chunked"``/``"pallas"`` on a non-ensemble model falls back to oracle.
    """
    ens = _packed_model(predictor)
    mode = _resolve_scorer(scorer, ens, space.n_candidates)
    if mode == "oracle":
        X = space.feature_matrix(predictor.spec, context)
        return np.asarray(predictor.predict_throughput_batch(X)), mode
    return (
        _score_grid_packed(
            ens, predictor.spec, space, context, chunk=chunk,
            pallas=(mode == "pallas"),
        ),
        mode,
    )


def recommend(
    predictor: IOPerformancePredictor,
    context: dict,
    space: ConfigSpace = DEFAULT_SPACE,
    top_k: int = 5,
    scorer: str = "auto",
    chunk: int = MEGA_GRID_CHUNK,
) -> List[dict]:
    """Ranked top-k configurations by predicted throughput.

    One grid scoring (see ``score_grid``) + an O(n) argpartition; only the k
    winning candidate dicts are built.  When the mega-grid path scored in
    float32 log space, the winners are re-scored through the oracle path so
    the reported ``predicted_throughput_mb_s`` values are identical to what
    the numpy baseline would report.
    """
    scores, mode = score_grid(predictor, context, space, scorer=scorer, chunk=chunk)
    n = scores.shape[0]
    k = min(top_k, n)
    if k < n:
        part = np.argpartition(-scores, k - 1)[:k]
        order = part[np.argsort(scores[part])[::-1]]
    else:
        order = np.argsort(scores)[::-1]
    winners = [space.candidate(i) for i in order]
    if mode == "oracle":
        pred_k = scores[order]
    else:
        names = predictor.spec.names
        Xk = np.empty((k, len(names)), np.float64)
        for r, cand in enumerate(winners):
            for j, name in enumerate(names):
                Xk[r, j] = (
                    float(cand[name]) if name in KNOB_NAMES
                    else float(context.get(name, 0.0))
                )
        pred_k = np.asarray(predictor.predict_throughput_batch(Xk))
        resort = np.argsort(-pred_k, kind="stable")
        winners = [winners[int(r)] for r in resort]
        pred_k = pred_k[resort]
    return [
        {**cand, "predicted_throughput_mb_s": float(pred_k[r])}
        for r, cand in enumerate(winners)
    ]


@dataclasses.dataclass
class AutotuneDecision:
    reconfigure: bool
    config: Optional[dict]
    predicted_gain: float
    current_throughput: float


class _ColumnStore:
    """Append-only observation matrix with amortized-doubling growth.

    Rows are feature dicts; columns are ``keys``.  ``matrix()``/``column()``
    return zero-copy views of the live buffer, so a refit never re-stacks
    history."""

    def __init__(self, keys: Sequence[str]):
        self.keys = tuple(keys)
        self._pos = {k: i for i, k in enumerate(self.keys)}
        self._buf = np.zeros((0, len(self.keys)), np.float64)
        self.n = 0

    def append(self, row: dict) -> None:
        if self.n == self._buf.shape[0]:
            grown = np.zeros((max(64, 2 * self._buf.shape[0]), len(self.keys)))
            grown[: self.n] = self._buf[: self.n]
            self._buf = grown
        out = self._buf[self.n]
        for k, v in row.items():
            i = self._pos.get(k)
            if i is not None:
                out[i] = float(v)
        self.n += 1

    def matrix(self, names: Sequence[str]) -> np.ndarray:
        """View of the first len(names) columns (requires ``names`` to be a
        prefix of ``keys``, which holds for spec.names + [target])."""
        assert tuple(names) == self.keys[: len(names)], "column order mismatch"
        return self._buf[: self.n, : len(names)]

    def column(self, key: str) -> np.ndarray:
        return self._buf[: self.n, self._pos[key]]

    def columns(self) -> Dict[str, np.ndarray]:
        return {k: self.column(k) for k in self.keys}


class OnlineAutotuner:
    """Streaming observation buffer + periodic refit + reconfiguration hints."""

    def __init__(
        self,
        spec: Optional[FeatureSpec] = None,
        space: ConfigSpace = DEFAULT_SPACE,
        refit_every: int = 20,
        min_observations: int = 24,
        gain_threshold: float = 0.10,  # propose only if >=10% predicted speedup
        model: str = "xgboost",
        seed: int = 0,
        min_config_diversity: int = 3,  # explore until this many distinct configs seen
        drift_threshold: float = 0.5,  # force refit if new-data median rel. error exceeds
        engine: Optional[str] = None,  # tree engine for refits (None = default)
    ):
        # default online view: paper features + prefetch knobs, so the
        # tuner can rank/learn prefetch_policy/lookahead/cache budget
        self.spec = spec or FeatureSpec(names=AUTOTUNE_FEATURE_NAMES)
        self.space = space
        self.refit_every = refit_every
        self.min_observations = min_observations
        self.gain_threshold = gain_threshold
        self.min_config_diversity = min_config_diversity
        self.drift_threshold = drift_threshold
        self.predictor = IOPerformancePredictor(
            self.spec, model=model, seed=seed, engine=engine
        )
        self._store = _ColumnStore(tuple(self.spec.names) + (self.spec.target,))
        self._since_fit = 0
        self._fitted = False
        # Hot-swap state: a refit builds the new model OFF the lock, then
        # publishes (model, generation) under it — snapshot() readers get a
        # consistent pair, and nothing ever observes a half-trained model.
        self._swap_lock = threading.Lock()
        self._generation = 0
        # Rollback state: the model the last refit displaced, republishable
        # via rollback() when a poisoned cycle slips past the ingest guard.
        self._prev_model = None
        self.rollbacks = 0
        self.degraded = False  # True while serving a rolled-back model
        self._explored: List[tuple] = []
        self._seen_keys: set = set()
        self._ingested_keys: set = set()  # (case_id, rep, seed) of campaign records
        self._drift_refit = False
        self.last_drift = float("nan")
        # Exploration order: deterministic permutation over the (cached)
        # candidate list, computed once instead of per decide() call.
        self._explore_order: Optional[np.ndarray] = None

    # Exogenous workload descriptors kept as features for the ONLINE tuner.
    # Endogenous measurements (throughput_mb_s, samples_per_second,
    # data_loading_ratio, iops) are *consequences* of the knobs — using them
    # as features online makes every candidate predict the current measured
    # value (the identity shortcut), so they are filtered here. The offline
    # IOPerformancePredictor keeps the paper's full 11-feature set.
    STATIC_KEYS = ("file_size_mb", "n_samples")

    def _filter_features(self, feats: dict, knobs: Optional[dict] = None) -> dict:
        keep = set(self._varied_knobs) | set(self.STATIC_KEYS)
        out = {k: float(v) for k, v in feats.items() if k in keep}
        if knobs:
            out.update({k: float(v) for k, v in knobs.items() if k in keep})
        return out

    # ------------------------------------------------------------------
    def _ingest(self, row: dict) -> None:
        self._store.append(row)
        self._seen_keys.add(self._config_key(row))
        self._since_fit += 1

    def seed_observations(self, rows: List[dict]):
        """Warm-start from an offline benchmark sweep (the paper's 141-row
        dataset): gives the predictor cross-configuration signal before any
        live telemetry arrives.

        Rows pass through the same endogenous-measurement filter as live
        ``observe()`` rows: offline rows carry real values in columns (e.g.
        ``samples_per_second``) that live telemetry zero-fills, and mixing the
        two would train the model on features it never sees at decision time.
        The *offline* ``IOPerformancePredictor`` keeps the paper's full
        11-feature path — the filter applies only to this online store."""
        for r in rows:
            row = self._filter_features(r)
            row[self.spec.target] = float(r.get(self.spec.target, 0.0))
            self._ingest(row)

    def ingest_records(self, records: Iterable[dict]) -> int:
        """Incrementally ingest campaign JSONL records (``campaign.py``
        schema: provenance + ``row``), skipping records already ingested.

        Records are keyed by ``(case_id, rep, seed)`` — the same identity the
        campaign runner and ``merge_records`` use — so the continuous loop can
        hand over the *full* merged record list every cycle and only the new
        rows land in the store.  Returns the number of rows ingested.

        Drift trigger: if a model is fitted, the prediction error on the new
        rows is measured *before* they are ingested; a median relative error
        above ``drift_threshold`` marks the model stale, and the next
        ``maybe_refit()`` fires regardless of the ``refit_every`` schedule.
        """
        fresh: List[dict] = []
        for rec in records:
            if rec.get("status") != "ok" or not rec.get("row"):
                continue
            key = (rec.get("case_id"), rec.get("rep", 0), rec.get("seed", 0))
            if key in self._ingested_keys:
                continue
            self._ingested_keys.add(key)
            fresh.append(rec["row"])
        if fresh:
            self._update_drift(fresh)
            self.seed_observations(fresh)
        return len(fresh)

    def _update_drift(self, rows: List[dict]) -> None:
        """Median relative prediction error of the current model on rows it
        has not seen — measured on the filtered (online) feature view."""
        if not self._fitted:
            return
        filtered = [self._filter_features(r) for r in rows]
        X = np.stack([self.spec.row(f) for f in filtered])
        y = np.asarray([float(r.get(self.spec.target, 0.0)) for r in rows])
        self.last_drift = float(np.median(self.predictor.relative_errors(X, y)))
        if self.last_drift > self.drift_threshold:
            self._drift_refit = True

    @property
    def _varied_knobs(self) -> tuple:
        return tuple(k for k in KNOB_NAMES if len(getattr(self.space, k)) > 1)

    def _config_key(self, cfg: dict) -> tuple:
        return tuple(cfg.get(k) for k in self._varied_knobs)

    def _diversity(self) -> int:
        return len(self._seen_keys)

    def mark_explored(self, config: dict) -> None:
        """Record that an exploration proposal was already issued for
        ``config`` — the resume path replays past explore decisions through
        this so a restarted tuner doesn't re-propose the same candidates."""
        key = self._config_key(config)
        if key not in self._explored:
            self._explored.append(key)

    def _next_unexplored(self, current: dict) -> Optional[dict]:
        seen = self._seen_keys | set(self._explored)
        seen.add(self._config_key(current))
        cands = self.space.candidates()  # cached on the space
        if self._explore_order is None:
            # deterministic shuffle: spread exploration across all knobs early
            self._explore_order = np.random.default_rng(1234).permutation(len(cands))
        for i in self._explore_order:
            if self._config_key(cands[i]) not in seen:
                self._explored.append(self._config_key(cands[i]))
                return cands[i]
        return None

    @property
    def n_observations(self) -> int:
        return self._store.n

    def observe(self, features: dict, target_throughput: float):
        row = self._filter_features(features)
        row[self.spec.target] = float(target_throughput)
        self._ingest(row)

    def _columns(self) -> dict:
        return self._store.columns()

    @property
    def fitted(self) -> bool:
        return self._fitted

    def maybe_refit(self) -> bool:
        if self._store.n < self.min_observations:
            return False
        if (
            self._fitted
            and not self._drift_refit
            and self._since_fit < self.refit_every
        ):
            return False
        # Zero-copy views of the live store: [n, F] feature block + target.
        # The (slow) fit happens off the swap lock against a fixed-length view
        # — concurrent appends only touch rows past n — and the result is
        # published atomically with its generation bump, so snapshot() readers
        # never see a half-trained model or a (model, generation) mismatch.
        model = self.predictor.build_model(
            self._store.matrix(self.spec.names),
            self._store.column(self.spec.target),
        )
        with self._swap_lock:
            self._prev_model = self.predictor.model if self._fitted else None
            self.predictor.model = model
            self._generation += 1
            self._fitted = True
            self.degraded = False  # a clean refit closes the circuit
        self._since_fit = 0
        self._drift_refit = False
        return True

    def rollback(self) -> bool:
        """Republish the model the last refit displaced (poisoned-cycle
        recovery): returns False when there is no previous generation.

        The generation bumps *forward* — never backward — so snapshot-derived
        cache keys invalidate exactly like a refit and no reader can conflate
        the restored model with the poisoned one it replaces.  The tuner is
        marked ``degraded`` until the next clean refit."""
        with self._swap_lock:
            if self._prev_model is None:
                return False
            self.predictor.model = self._prev_model
            self._prev_model = None  # one level of undo, not a history
            self._generation += 1
            self.rollbacks += 1
            self.degraded = True
        # A rollback means the latest observations produced a bad model —
        # force drift-triggered refit consideration once newer data arrives.
        self._since_fit = 0
        return True

    @property
    def generation(self) -> int:
        """Monotonic model generation: 0 until the first fit, then +1 per
        completed refit.  Cache keys derived from it invalidate atomically
        the instant a refit publishes (``snapshot()`` hands out the pair)."""
        return self._generation

    def snapshot(self) -> Optional[PredictorSnapshot]:
        """Consistent ``(model, generation)`` view for concurrent scoring, or
        ``None`` until the first fit.  Successive refits never mutate a
        published snapshot's model — in-flight work finishes on the model it
        started with (the serving tier's no-mixed-batch guarantee)."""
        with self._swap_lock:
            if not self._fitted:
                return None
            return self.predictor.snapshot(self._generation)

    def filter_context(self, context: dict, knobs: Optional[dict] = None) -> dict:
        """Public view of the online feature filter (see ``_filter_features``):
        the serving tier must featurize exactly like ``ranked()``/``decide()``
        or batched results would diverge from the in-process path."""
        return self._filter_features(context, knobs=knobs)

    def ranked(self, context: dict, top_k: int = 5) -> List[dict]:
        """Ranked top-k candidate configs under the live (filtered) context —
        the continuous loop's re-recommend report.  Empty until fitted."""
        if not self._fitted:
            return []
        return recommend(
            self.predictor, self._filter_features(context), self.space, top_k=top_k
        )

    def decide(
        self,
        current_config: dict,
        context: dict,
        best: Optional[dict] = None,
    ) -> AutotuneDecision:
        """Given live context telemetry, propose the best predicted config.

        Cold start: until ``min_config_diversity`` distinct configs have been
        observed the model has no cross-config signal, so we EXPLORE —
        propose the next unexplored candidate instead of exploiting.

        ``best`` short-circuits the internal top-1 grid inference with an
        already-ranked winner (callers that just computed ``ranked()`` pass
        ``ranked(...)[0]`` to avoid scoring the grid twice).
        """
        cur = float(context.get("throughput_mb_s", 0.0))
        if self._diversity() < self.min_config_diversity:
            cand = self._next_unexplored(current_config)
            if cand is not None:
                return AutotuneDecision(True, {**cand, "explore": True}, 0.0, cur)
        if not self._fitted:
            return AutotuneDecision(False, None, 0.0, cur)
        if best is None:
            best = self.ranked(context, top_k=1)[0]
        cur_pred = self.predictor.predict_throughput(
            self._filter_features(context, knobs=current_config)
        )
        base = max(cur_pred, 1e-9)
        gain = (best["predicted_throughput_mb_s"] - base) / base
        # Compare over the *varied knobs* only: a knob missing from the
        # trainer's dict must count as a difference (not be skipped), and
        # extra non-knob keys (labels, annotations) must not force a
        # spurious "different config" verdict.
        same = all(best.get(k) == current_config.get(k) for k in self._varied_knobs)
        if not same and gain >= self.gain_threshold:
            return AutotuneDecision(True, best, float(gain), cur)
        return AutotuneDecision(False, None, float(gain), cur)
