"""The paper's workflow end-to-end on real (fast-collected) observations,
plus autotuner behaviour."""

import numpy as np
import pytest

from repro.core import (
    ConfigSpace,
    IOPerformancePredictor,
    OnlineAutotuner,
    accuracy,
    make_classifier,
    recommend,
)


def test_predictor_fast_observations(obs_fast):
    rows, cols = obs_fast
    pred = IOPerformancePredictor(model="xgboost")
    reports = pred.evaluate_zoo(cols, models=["xgboost", "linear"], with_cv=False)
    # obs_fast is live-collected benchmark data, so cross-model R2 ordering
    # is unstable under suite load (no fixed margin holds reliably); assert
    # only the stable facts — both models fit the data.  The full-141 Fig-5
    # ordering is asserted in benchmarks / EXPERIMENTS.md.
    assert reports["xgboost"].train_r2 > 0.9
    assert reports["xgboost"].test_r2 > 0.5
    assert reports["linear"].train_r2 > 0.5


def test_predict_throughput_scalar(obs_fast):
    rows, cols = obs_fast
    pred = IOPerformancePredictor(model="xgboost").fit(cols)
    t = pred.predict_throughput(
        {"batch_size": 32, "num_workers": 2, "block_kb": 64, "throughput_mb_s": 500.0}
    )
    assert np.isfinite(t) and t >= 0


def test_recommend_ranks_by_prediction(obs_fast):
    rows, cols = obs_fast
    pred = IOPerformancePredictor(model="xgboost").fit(cols)
    space = ConfigSpace(batch_size=(16, 64), num_workers=(0, 2), block_kb=(4, 64),
                        n_threads=(1,), prefetch_depth=(1,))
    top = recommend(pred, context={"throughput_mb_s": 800.0, "file_size_mb": 16.0},
                    space=space, top_k=4)
    assert len(top) == 4
    scores = [t["predicted_throughput_mb_s"] for t in top]
    assert scores == sorted(scores, reverse=True)


def test_online_autotuner_reconfigures_on_clear_signal():
    """Synthetic world: more workers => strictly higher throughput."""
    tuner = OnlineAutotuner(
        refit_every=1, min_observations=10, gain_threshold=0.05,
        space=ConfigSpace(batch_size=(32,), num_workers=(0, 2, 4),
                          block_kb=(64,), n_threads=(1,), prefetch_depth=(1,)),
        seed=0,
    )
    rng = np.random.default_rng(0)
    for i in range(40):
        w = int(rng.choice([0, 2, 4]))
        thr = 100.0 * (1 + w) * (1 + 0.01 * rng.normal())
        tuner.observe(
            {"batch_size": 32, "num_workers": w, "block_kb": 64,
             "throughput_mb_s": thr, "samples_per_second": thr * 2,
             "data_loading_ratio": 0.5 / (1 + w)},
            thr,
        )
    assert tuner.maybe_refit()
    decision = tuner.decide(
        current_config={"batch_size": 32, "num_workers": 0, "block_kb": 64,
                        "prefetch_depth": 1},
        context={"batch_size": 32, "num_workers": 0, "block_kb": 64,
                 "throughput_mb_s": 100.0, "samples_per_second": 200.0,
                 "data_loading_ratio": 0.5},
    )
    assert decision.reconfigure
    assert decision.config["num_workers"] == 4


def test_autotuner_no_churn_when_already_best():
    tuner = OnlineAutotuner(
        refit_every=1, min_observations=5, gain_threshold=0.10,
        space=ConfigSpace(batch_size=(32,), num_workers=(0, 4), block_kb=(64,),
                          n_threads=(1,), prefetch_depth=(1,)),
    )
    for w, thr in [(0, 100), (4, 500)] * 5:
        tuner.observe({"batch_size": 32, "num_workers": w, "block_kb": 64,
                       "throughput_mb_s": thr}, thr)
    tuner.maybe_refit()
    d = tuner.decide(
        current_config={"batch_size": 32, "num_workers": 4, "block_kb": 64,
                        "prefetch_depth": 1},
        context={"batch_size": 32, "num_workers": 4, "block_kb": 64,
                 "throughput_mb_s": 500.0},
    )
    assert not d.reconfigure


def test_format_classifier_rq3():
    """RQ3: classifiers recommend the best format from workload features."""
    rng = np.random.default_rng(0)
    n = 400
    X = np.stack([
        rng.uniform(1, 4096, n),   # record_kb
        rng.uniform(0, 1, n),      # compressibility
        rng.uniform(0, 1, n),      # random-access fraction
    ], axis=1)
    # ground truth: compressed if compressible, raw if tiny records, packed else
    y = np.where(X[:, 1] > 0.7, 2, np.where(X[:, 0] < 64, 0, 1))
    for name in ("logistic", "random_forest", "gbt"):
        m = make_classifier(name, n_classes=3)
        m.fit(X, y)
        acc = accuracy(y, m.predict(X))
        assert acc > (0.85 if name != "logistic" else 0.7), (name, acc)


def test_config_space_cached_grid_consistent():
    """The cached zero-copy feature matrix must agree row-for-row with the
    old per-candidate dict-merge featurization, and candidate(i) with
    candidates()[i]."""
    from repro.core.features import FeatureSpec

    spec = FeatureSpec()
    space = ConfigSpace(batch_size=(16, 64), num_workers=(0, 2), block_kb=(4, 64),
                        n_threads=(1, 2), prefetch_depth=(1, 2))
    ctx = {"throughput_mb_s": 800.0, "file_size_mb": 16.0}
    X = space.feature_matrix(spec, ctx)
    cands = space.candidates()
    assert X.shape == (space.n_candidates, spec.n_features)
    expected = np.stack([spec.row({**ctx, **c}) for c in cands])
    np.testing.assert_array_equal(X, expected)
    for i in (0, 7, len(cands) - 1):
        assert space.candidate(i) == cands[i]
    # a second call with new context rewrites only context columns
    X2 = space.feature_matrix(spec, {"throughput_mb_s": 5.0})
    expected2 = np.stack([spec.row({"throughput_mb_s": 5.0, **c}) for c in cands])
    np.testing.assert_array_equal(X2, expected2)


def _two_worker_tuner(gain_threshold=0.10, **kw):
    """Fitted tuner on a world where num_workers=4 beats num_workers=0 5x."""
    tuner = OnlineAutotuner(
        refit_every=1, min_observations=5, gain_threshold=gain_threshold,
        space=ConfigSpace(batch_size=(32,), num_workers=(0, 4), block_kb=(64,),
                          n_threads=(1,), prefetch_depth=(1,)),
        **kw,
    )
    for w, thr in [(0, 100.0), (4, 500.0)] * 5:
        tuner.observe({"batch_size": 32, "num_workers": w, "block_kb": 64,
                       "throughput_mb_s": thr}, thr)
    assert tuner.maybe_refit()
    return tuner


def test_decide_missing_knob_counts_as_difference():
    """Regression: a varied knob absent from the trainer's config dict used to
    be skipped by the same-config check, so the genuinely better config was
    reported as 'same' and never proposed."""
    tuner = _two_worker_tuner()
    d = tuner.decide(
        current_config={"batch_size": 32, "block_kb": 64},  # num_workers missing
        context={"batch_size": 32, "block_kb": 64, "throughput_mb_s": 100.0},
    )
    assert d.reconfigure
    assert d.config["num_workers"] == 4


def test_decide_extra_keys_do_not_force_mismatch():
    """Regression: non-knob keys (labels, annotations) in the trainer's config
    used to force a spurious 'different config' verdict; with the current
    config already the best, no reconfiguration must be proposed even at a
    zero gain threshold."""
    tuner = _two_worker_tuner(gain_threshold=0.0)
    d = tuner.decide(
        current_config={"batch_size": 32, "num_workers": 4, "block_kb": 64,
                        "label": "trial-7", "explore": True},
        context={"batch_size": 32, "num_workers": 4, "block_kb": 64,
                 "throughput_mb_s": 500.0},
    )
    assert not d.reconfigure


def test_seeded_and_live_rows_produce_identical_store_columns():
    """Regression: seed_observations used to ingest raw offline rows, leaving
    real values in endogenous columns that live observe() rows zero-fill — a
    train/serve skew that poisoned every refit of the continuous loop."""
    space = ConfigSpace(batch_size=(32,), num_workers=(0, 2), block_kb=(64,),
                        n_threads=(1,), prefetch_depth=(1,))
    offline_row = {
        "batch_size": 32, "num_workers": 2, "block_kb": 64,
        "file_size_mb": 8.0, "n_samples": 100,
        # endogenous measurements a live row can't provide as features:
        "samples_per_second": 123.0, "data_loading_ratio": 0.4,
        "throughput_mb_s": 456.0, "iops": 1e4,
        "target_throughput": 300.0, "backend": "tmpfs", "bench_type": "pipeline",
    }
    seeded = OnlineAutotuner(space=space)
    seeded.seed_observations([offline_row])
    live = OnlineAutotuner(space=space)
    live.observe({k: v for k, v in offline_row.items()
                  if k != "target_throughput"}, 300.0)
    np.testing.assert_array_equal(
        seeded._store.matrix(seeded.spec.names),
        live._store.matrix(live.spec.names),
    )
    np.testing.assert_array_equal(
        seeded._store.column(seeded.spec.target),
        live._store.column(live.spec.target),
    )
    # the endogenous columns specifically must be zero in the seeded store
    for col in ("samples_per_second", "data_loading_ratio",
                "throughput_mb_s", "iops"):
        assert (seeded._store.column(col) == 0).all(), col


def _campaign_record(case_id, seed, row):
    return {"case_id": case_id, "rep": 0, "seed": seed, "status": "ok",
            "row": row}


def _worker_rows(seed, scale=1.0):
    return [
        _campaign_record(f"c-w{w}-b{b}", seed, {
            "batch_size": b, "num_workers": w, "block_kb": 64,
            "file_size_mb": 8.0, "target_throughput": scale * 100.0 * (1 + w),
        })
        for w in (0, 2, 4) for b in (16, 32)
    ]


def test_ingest_records_dedups_by_key():
    tuner = OnlineAutotuner(min_observations=4,
                            space=ConfigSpace(batch_size=(16, 32),
                                              num_workers=(0, 2, 4),
                                              block_kb=(64,), n_threads=(1,),
                                              prefetch_depth=(1,)))
    recs = _worker_rows(seed=0)
    assert tuner.ingest_records(recs) == 6
    assert tuner.ingest_records(recs) == 0  # same (case_id, rep, seed) keys
    assert tuner.n_observations == 6
    # error records and new seeds behave as expected
    recs2 = _worker_rows(seed=1)
    recs2[0]["status"] = "error"
    recs2[0]["row"] = None
    assert tuner.ingest_records(recs2) == 5
    assert tuner.n_observations == 11


def test_drift_forces_refit_off_schedule():
    """A regime shift in new data must trigger a refit even when the
    refit_every schedule is nowhere near due."""
    space = ConfigSpace(batch_size=(16, 32), num_workers=(0, 2, 4),
                        block_kb=(64,), n_threads=(1,), prefetch_depth=(1,))
    tuner = OnlineAutotuner(space=space, refit_every=10_000,
                            min_observations=4, drift_threshold=0.3)
    tuner.ingest_records(_worker_rows(seed=0))
    assert tuner.maybe_refit()  # initial fit
    # same-regime data: low drift, schedule far away -> no refit
    tuner.ingest_records(_worker_rows(seed=1))
    assert tuner.last_drift < 0.3
    assert not tuner.maybe_refit()
    # regime shift: storage got 5x faster -> drift fires a refit
    tuner.ingest_records(_worker_rows(seed=2, scale=5.0))
    assert tuner.last_drift > 0.3
    assert tuner.maybe_refit()
    assert not tuner.maybe_refit()  # drift flag cleared by the refit


def test_online_autotuner_column_store_matches_rows():
    """The incremental store's zero-copy matrix equals the stack-from-dicts
    path the refit used to take."""
    tuner = OnlineAutotuner(min_observations=4, refit_every=1,
                            space=ConfigSpace(batch_size=(32,), num_workers=(0, 2),
                                              block_kb=(64,), n_threads=(1,),
                                              prefetch_depth=(1,)))
    rng = np.random.default_rng(0)
    for i in range(12):
        w = int(rng.choice([0, 2]))
        tuner.observe({"batch_size": 32, "num_workers": w, "block_kb": 64,
                       "file_size_mb": 8.0}, 100.0 * (1 + w))
    cols = tuner._columns()
    spec = tuner.spec
    X_store = tuner._store.matrix(spec.names)
    X_dict = spec.matrix(cols)
    np.testing.assert_array_equal(X_store, X_dict)
    assert tuner._store.column(spec.target).shape == (12,)
    assert (tuner._store.column(spec.target) > 0).all()
    assert tuner.maybe_refit()
    assert tuner.n_observations == 12


def test_autotuner_refit_honors_repro_tree_engine_env(monkeypatch):
    """REPRO_TREE_ENGINE set *after* import must steer OnlineAutotuner
    refits: engine resolution happens at fit time, not import time."""
    from repro.core import tree as tree_mod

    calls = []
    real = tree_mod._ENGINES["reference"]

    def spy(*args, **kwargs):
        calls.append("reference")
        return real(*args, **kwargs)

    monkeypatch.setitem(tree_mod._ENGINES, "reference", spy)
    monkeypatch.setenv("REPRO_TREE_ENGINE", "reference")
    tuner = OnlineAutotuner(
        refit_every=1, min_observations=8,
        space=ConfigSpace(batch_size=(32,), num_workers=(0, 2),
                          block_kb=(64,), n_threads=(1,), prefetch_depth=(1,)),
    )
    rng = np.random.default_rng(0)
    for _ in range(10):
        w = int(rng.choice([0, 2]))
        thr = 50.0 * (1 + w)
        tuner.observe({"batch_size": 32, "num_workers": w, "block_kb": 64}, thr)
    assert tuner.maybe_refit()
    assert calls, "refit did not route through the engine named by REPRO_TREE_ENGINE"


def test_predictor_engine_argument_overrides_env(monkeypatch):
    """An explicit engine= on the predictor beats REPRO_TREE_ENGINE."""
    from repro.core import FEATURE_NAMES, tree as tree_mod
    from repro.core.predictor import IOPerformancePredictor

    calls = []
    real = tree_mod._ENGINES["level"]

    def spy(*args, **kwargs):
        calls.append("level")
        return real(*args, **kwargs)

    monkeypatch.setitem(tree_mod._ENGINES, "level", spy)
    monkeypatch.setenv("REPRO_TREE_ENGINE", "reference")
    rng = np.random.default_rng(1)
    cols = {name: rng.random(40) * 10 for name in FEATURE_NAMES}
    cols["target_throughput"] = rng.random(40) * 100 + 10
    IOPerformancePredictor(model="xgboost", engine="level").fit(cols)
    assert calls, "explicit engine= was not honored"


def test_recommend_spans_in_a_profiler_trace(tmp_path):
    """A mega-grid recommend() under jax.profiler shows one repro.recommend
    span holding one assemble, one dispatch and one fetch, in that order,
    whatever the grid's size, and then one repro.recommend.select; an
    active trace changes no pick."""
    import jax
    from jax.profiler import ProfileData

    from repro.core import FEATURE_NAMES
    from repro.core.autotune import MEGA_GRID_CHUNK, MEGA_GRID_MIN, RECOMMEND_SPANS

    rng = np.random.default_rng(0)
    cols = {name: rng.uniform(1, 100, 240) for name in FEATURE_NAMES}
    cols["target_throughput"] = rng.uniform(10, 500, 240) + 2.0 * cols[FEATURE_NAMES[0]]
    pred = IOPerformancePredictor(model="xgboost").fit(cols)
    space = ConfigSpace(prefetch_policy=(0, 1), lookahead_batches=(4, 8),
                        cache_budget_mb=(32.0, 64.0))
    n = space.n_candidates
    assert n >= MEGA_GRID_MIN and n > MEGA_GRID_CHUNK  # more than one block
    ctx = {"throughput_mb_s": 800.0, "file_size_mb": 64.0}
    untraced = recommend(pred, ctx, space, top_k=5, scorer="chunked")
    jax.profiler.start_trace(str(tmp_path))
    try:
        traced = recommend(pred, ctx, space, top_k=5, scorer="chunked")
    finally:
        jax.profiler.stop_trace()
    assert traced == untraced

    (path,) = tmp_path.rglob("*.xplane.pb")
    spans = sorted((e.start_ns, e.end_ns, e.name)
                   for plane in ProfileData.from_file(str(path)).planes
                   if plane.name.startswith("/host:")
                   for line in plane.lines for e in line.events
                   if e.name in RECOMMEND_SPANS)
    by_name = {name: [(s, e) for s, e, nm in spans if nm == name] for name in RECOMMEND_SPANS}
    call, assemble, dispatch, fetch, select = (by_name[name] for name in RECOMMEND_SPANS)
    assert len(call) == 1 and len(select) == 1
    # one grid program per call: the engagement counter of the device path
    assert len(assemble) == len(dispatch) == len(fetch) == 1
    lo, hi = call[0]
    phases = assemble + dispatch + fetch + select
    assert all(lo <= s <= e <= hi for s, e in phases)
    # one after another, never overlapping: assemble, dispatch, fetch, select
    assert all(a[1] <= b[0] for a, b in zip(phases, phases[1:]))
