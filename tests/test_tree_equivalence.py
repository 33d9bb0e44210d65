"""Golden equivalence: the level-wise and batched tree engines must reproduce
the reference DFS builder *exactly* — same arrays, same node numbering, same
leaf routing — on the paper model configs and across a property sweep of
builder settings.  (The oracle stays available via engine="reference" /
REPRO_TREE_ENGINE; the batched engine additionally proves its native-C and
pure-numpy code paths identical.)"""

import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.core import GBTBinaryClassifier, GBTConfig, GBTRegressor, RandomForestRegressor, RFConfig
from repro.core import _native
from repro.core.tree import (
    BinnedData,
    TreeBuilderConfig,
    bin_features,
    build_forest_batched,
    build_tree,
    build_tree_with_leaves,
    compute_bins,
    resolve_engine,
)

TREE_FIELDS = ("feature", "threshold", "left", "right", "value", "gain", "cover")
ENSEMBLE_FIELDS = ("feature", "threshold", "left", "right", "value")


def _assert_trees_identical(ta, tb):
    for f in TREE_FIELDS:
        np.testing.assert_array_equal(
            getattr(ta, f), getattr(tb, f), err_msg=f"tree field {f!r} differs"
        )


def _assert_ensembles_identical(ea, eb):
    for f in ENSEMBLE_FIELDS:
        np.testing.assert_array_equal(
            np.asarray(getattr(ea, f)), np.asarray(getattr(eb, f)),
            err_msg=f"ensemble field {f!r} differs",
        )
    assert ea.base_score == eb.base_score and ea.scale == eb.scale


def _data(n=260, d=11, seed=0):
    rng = np.random.default_rng(seed)
    X = rng.uniform(-2, 2, (n, d))
    y = np.sin(2 * X[:, 0]) + X[:, 1] ** 2 + 0.5 * X[:, 2] * X[:, 3]
    y = y + 0.05 * rng.normal(size=n)
    return X, y


# ---------------------------------------------------------------- paper configs


def test_gbt_paper_config_engines_identical():
    """Paper §3.3.2 GBT (depth 6, lr 0.1, subsample 0.8): byte-identical fit."""
    X, y = _data()
    cfg = GBTConfig(n_estimators=12, seed=3)  # paper hyperparams, fewer rounds
    m_ref = GBTRegressor(cfg, engine="reference").fit(X, y)
    for engine in ("level", "batched"):
        m_e = GBTRegressor(cfg, engine=engine).fit(X, y)
        _assert_ensembles_identical(m_e.ensemble, m_ref.ensemble)
        np.testing.assert_array_equal(
            m_e.feature_importances_, m_ref.feature_importances_
        )
        np.testing.assert_array_equal(m_e.predict(X), m_ref.predict(X))


def test_rf_paper_config_engines_identical():
    """Paper §3.3.2 RF (depth 10, min_samples_split 5): byte-identical fit."""
    X, y = _data()
    cfg = RFConfig(n_estimators=8, seed=5)  # paper tree params, fewer trees
    m_ref = RandomForestRegressor(cfg, engine="reference").fit(X, y)
    for engine in ("level", "batched"):
        m_e = RandomForestRegressor(cfg, engine=engine).fit(X, y)
        _assert_ensembles_identical(m_e.ensemble, m_ref.ensemble)
        np.testing.assert_array_equal(m_e.predict(X), m_ref.predict(X))


def test_gbt_classifier_engines_identical():
    rng = np.random.default_rng(7)
    X = rng.normal(size=(220, 5))
    y = (X[:, 0] + X[:, 1] ** 2 > 0.4).astype(np.float64)
    cfg = GBTConfig(n_estimators=10, max_depth=3, seed=0)
    m_ref = GBTBinaryClassifier(cfg, engine="reference").fit(X, y)
    for engine in ("level", "batched"):
        m_e = GBTBinaryClassifier(cfg, engine=engine).fit(X, y)
        _assert_ensembles_identical(m_e.ensemble, m_ref.ensemble)
        np.testing.assert_array_equal(m_e.predict_proba(X), m_ref.predict_proba(X))


def test_default_engine_is_batched_and_flag_gated(monkeypatch):
    from repro.core import tree as tree_mod

    assert tree_mod.DEFAULT_ENGINE in tree_mod._ENGINES
    assert set(tree_mod._ENGINES) == {"batched", "level", "reference"}
    with pytest.raises(ValueError, match="unknown tree engine"):
        build_tree(np.zeros((4, 2), np.uint16), [np.array([0.5])] * 2,
                   np.zeros(4), np.ones(4), TreeBuilderConfig(), engine="nope")
    # resolve_engine precedence: explicit beats env beats built-in default,
    # and the env var is re-read at call time (not import time).
    monkeypatch.delenv("REPRO_TREE_ENGINE", raising=False)
    assert resolve_engine() == "batched"
    monkeypatch.setenv("REPRO_TREE_ENGINE", "reference")
    assert resolve_engine() == "reference"
    assert resolve_engine("level") == "level"


# ---------------------------------------------------------------- single trees


def _tree_case(n, d, depth, bins, seed, zero_frac=0.0, int_hess=False, round_X=False):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, d))
    if round_X:
        X = np.round(X)  # heavy bin ties -> exercises tie-breaking
    y = rng.normal(size=n)
    g = -(y - y.mean())
    h = np.ones(n)
    if int_hess:  # RF-style bootstrap weights (including zeros)
        h = rng.integers(0, 3, n).astype(np.float64)
        g = g * h
    elif zero_frac > 0.0:  # GBT subsample-style zeroed rows
        mask = rng.random(n) < (1.0 - zero_frac)
        g, h = np.where(mask, g, 0.0), np.where(mask, h, 0.0)
    edges = compute_bins(X, bins)
    Xb = bin_features(X, edges)
    cfg = TreeBuilderConfig(max_depth=depth, max_bins=bins)
    return Xb, edges, g, h, cfg


def _assert_engines_match(Xb, edges, g, h, cfg):
    t_ref, leaf_ref = build_tree_with_leaves(Xb, edges, g, h, cfg, engine="reference")
    for engine in ("level", "batched"):
        t_e, leaf_e = build_tree_with_leaves(Xb, edges, g, h, cfg, engine=engine)
        _assert_trees_identical(t_ref, t_e)
        np.testing.assert_array_equal(leaf_ref, leaf_e, err_msg=f"engine {engine!r}")
        # every routed leaf really is a leaf
        assert (t_e.feature[leaf_e] == -1).all()
    return t_ref


def test_leaf_assignment_matches_reference_and_is_terminal():
    Xb, edges, g, h, cfg = _tree_case(300, 6, 6, 32, seed=1, zero_frac=0.25)
    _assert_engines_match(Xb, edges, g, h, cfg)


def test_binned_data_reuse_matches_plain_arrays():
    """Passing a prebuilt BinnedData (the ensemble fast path) changes nothing."""
    Xb, edges, g, h, cfg = _tree_case(200, 5, 5, 24, seed=2)
    data = BinnedData.build(Xb, edges)
    t_plain, leaf_plain = build_tree_with_leaves(Xb, edges, g, h, cfg)
    for _ in range(2):  # scratch buffers are reused across calls
        t_data, leaf_data = build_tree_with_leaves(data, None, g, h, cfg)
        _assert_trees_identical(t_plain, t_data)
        np.testing.assert_array_equal(leaf_plain, leaf_data)


def test_constant_feature_and_tiny_n():
    for n in (1, 2, 5):
        rng = np.random.default_rng(n)
        X = np.column_stack([np.ones(n), rng.normal(size=n)])
        y = rng.normal(size=n)
        edges = compute_bins(X, 8)
        Xb = bin_features(X, edges)
        cfg = TreeBuilderConfig(max_depth=3, max_bins=8)
        _assert_engines_match(Xb, edges, -(y - y.mean()), np.ones(n), cfg)


# ---------------------------------------------------------------- property sweep


@settings(max_examples=25, deadline=None)
@given(
    n=st.integers(5, 300),
    d=st.integers(1, 7),
    depth=st.integers(1, 10),
    bins=st.integers(2, 72),
    seed=st.integers(0, 10_000),
    flavor=st.sampled_from(["plain", "rounded", "zeros", "int_hess"]),
)
def test_engine_equivalence_property(n, d, depth, bins, seed, flavor):
    """Bit-identical trees across depths/bins/row-weight patterns.

    Covers both histogram layouts of the level engine (dense frontier and
    candidate-compacted) since depth ranges beyond the dense cutoff."""
    Xb, edges, g, h, cfg = _tree_case(
        n, d, depth, bins, seed,
        zero_frac=0.3 if flavor == "zeros" else 0.0,
        int_hess=flavor == "int_hess",
        round_X=flavor == "rounded",
    )
    _assert_engines_match(Xb, edges, g, h, cfg)


@settings(max_examples=10, deadline=None)
@given(
    seed=st.integers(0, 10_000),
    min_child_weight=st.sampled_from([1e-3, 0.5, 1.0, 5.0]),
    reg_lambda=st.sampled_from([0.25, 1.0, 3.0]),
    gamma=st.sampled_from([0.0, 0.05, 0.5]),
    min_samples_split=st.integers(2, 12),
)
def test_engine_equivalence_regularizers_property(
    seed, min_child_weight, reg_lambda, gamma, min_samples_split
):
    rng = np.random.default_rng(seed)
    n = 180
    X = rng.normal(size=(n, 5))
    y = rng.normal(size=n)
    edges = compute_bins(X, 32)
    Xb = bin_features(X, edges)
    cfg = TreeBuilderConfig(
        max_depth=6,
        min_samples_split=min_samples_split,
        min_child_weight=min_child_weight,
        reg_lambda=reg_lambda,
        gamma=gamma,
        max_bins=32,
    )
    _assert_engines_match(Xb, edges, -(y - y.mean()), np.ones(n), cfg)


# ---------------------------------------------------------------- batched engine


def _rf_data(n=500, d=8, seed=11):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, d))
    y = X[:, 0] * 2 - X[:, 1] ** 2 + 0.1 * rng.normal(size=n)
    return X, y


def test_build_forest_batched_matches_reference_per_tree():
    """The ensemble API grows every tree bit-identically to per-tree
    reference builds on the same (grad, hess) rows (RF bootstrap weights)."""
    X, y = _rf_data()
    n = X.shape[0]
    rng = np.random.default_rng(3)
    edges = compute_bins(X, 32)
    data = BinnedData.build(bin_features(X, edges), edges)
    cfg = TreeBuilderConfig(max_depth=8, min_samples_split=5,
                            min_child_weight=1.0, reg_lambda=0.0, max_bins=32)
    W = np.stack([
        np.bincount(rng.integers(0, n, n), minlength=n).astype(np.float64)
        for _ in range(6)
    ])
    grads = -(y - y.mean())[None, :] * W
    for t, (tree, leaf) in enumerate(build_forest_batched(data, grads, W, cfg)):
        t_ref, leaf_ref = build_tree_with_leaves(
            data, None, grads[t], W[t], cfg, engine="reference"
        )
        _assert_trees_identical(t_ref, tree)
        np.testing.assert_array_equal(leaf_ref, leaf, err_msg=f"tree {t}")


def test_rf_all_engines_identical_bootstrap():
    """RF fit (bootstrap weights, colsample=1.0) is bit-identical across all
    three engines — the batched path pre-draws the same bootstrap stream."""
    X, y = _rf_data(400, 6)
    cfg = RFConfig(n_estimators=7, max_depth=7, seed=9)
    m_ref = RandomForestRegressor(cfg, engine="reference").fit(X, y)
    for engine in ("level", "batched"):
        m_e = RandomForestRegressor(cfg, engine=engine).fit(X, y)
        _assert_ensembles_identical(m_e.ensemble, m_ref.ensemble)
        np.testing.assert_array_equal(
            m_e.feature_importances_, m_ref.feature_importances_
        )


def test_rf_colsample_engines_equivalent():
    """With colsample < 1.0 all three engines are bit-identical: per-node
    feature subsets are keyed on (per-tree base key, heap path), so the DFS,
    frontier, and lockstep traversal orders draw the same subsets, and the
    batched RF path replays the per-tree (bootstrap, base-key) stream in one
    lockstep build — the PR 5 caveat is closed."""
    X, y = _rf_data(600, 8, seed=21)
    cfg = RFConfig(n_estimators=30, max_depth=7, colsample=0.5, seed=2)
    m_ref = RandomForestRegressor(cfg, engine="reference").fit(X, y)
    for engine in ("level", "batched"):
        m_e = RandomForestRegressor(cfg, engine=engine).fit(X, y)
        _assert_ensembles_identical(m_e.ensemble, m_ref.ensemble)
        np.testing.assert_array_equal(
            m_e.feature_importances_, m_ref.feature_importances_
        )


def test_batched_single_tree_colsample_replays_level_engine():
    """B=1 batched builds consume the column-sampling RNG in the level
    engine's frontier order, so single-tree colsample fits replay exactly."""
    rng = np.random.default_rng(5)
    n, d = 300, 8
    X = rng.normal(size=(n, d))
    y = rng.normal(size=n)
    edges = compute_bins(X, 24)
    Xb = bin_features(X, edges)
    cfg = TreeBuilderConfig(max_depth=6, max_bins=24)
    g = -(y - y.mean())
    h = np.ones(n)
    t_lvl, leaf_lvl = build_tree_with_leaves(
        Xb, edges, g, h, cfg, rng=np.random.default_rng(77), colsample=0.5,
        engine="level",
    )
    t_bat, leaf_bat = build_tree_with_leaves(
        Xb, edges, g, h, cfg, rng=np.random.default_rng(77), colsample=0.5,
        engine="batched",
    )
    _assert_trees_identical(t_lvl, t_bat)
    np.testing.assert_array_equal(leaf_lvl, leaf_bat)


def test_batched_numpy_fallback_matches_native(monkeypatch):
    """With the native kernels disabled the pure-numpy layouts must produce
    the same trees (the equivalence that keeps no-compiler platforms safe)."""
    X, y = _rf_data(350, 7, seed=31)
    cfg = RFConfig(n_estimators=4, max_depth=9, seed=1)
    m_native = RandomForestRegressor(cfg, engine="batched").fit(X, y)
    monkeypatch.setattr(_native, "_tried", True)
    monkeypatch.setattr(_native, "_lib", None)
    assert not _native.available()
    m_numpy = RandomForestRegressor(cfg, engine="batched").fit(X, y)
    _assert_ensembles_identical(m_native.ensemble, m_numpy.ensemble)


# ------------------------------------------------------------- threaded kernels


def _fit_with_threads(monkeypatch, ctor, X, y, nt):
    monkeypatch.setenv("REPRO_NATIVE_THREADS", str(nt))
    return ctor().fit(X, y)


def test_rf_paper_threads_byte_identical(monkeypatch):
    """Determinism hammer: the paper RF config fit at REPRO_NATIVE_THREADS
    in {1, 2, 4} is byte-identical (ownership partitioning: every node is
    processed end-to-end by one thread, so no reduction order changes)."""
    X, y = _data(400, 11, seed=13)
    cfg = RFConfig(n_estimators=12, seed=4)  # paper depth/min_samples_split
    ctor = lambda: RandomForestRegressor(cfg, engine="batched")
    base = _fit_with_threads(monkeypatch, ctor, X, y, 1)
    for nt in (2, 4):
        m = _fit_with_threads(monkeypatch, ctor, X, y, nt)
        _assert_ensembles_identical(base.ensemble, m.ensemble)
        np.testing.assert_array_equal(
            base.feature_importances_, m.feature_importances_
        )


def test_gbt_paper_threads_byte_identical(monkeypatch):
    """Paper GBT config (subsample 0.8) at threads in {1, 2, 4}: identical."""
    X, y = _data(400, 11, seed=23)
    cfg = GBTConfig(n_estimators=10, seed=6)
    ctor = lambda: GBTRegressor(cfg, engine="batched")
    base = _fit_with_threads(monkeypatch, ctor, X, y, 1)
    for nt in (2, 4):
        m = _fit_with_threads(monkeypatch, ctor, X, y, nt)
        _assert_ensembles_identical(base.ensemble, m.ensemble)


def test_rf_colsample_threads_byte_identical(monkeypatch):
    """colsample<1 + threads: the keyed column draws are thread-count
    independent, so the hardest combination is still byte-identical."""
    X, y = _rf_data(300, 8, seed=41)
    cfg = RFConfig(n_estimators=6, max_depth=7, colsample=0.5, seed=3)
    ctor = lambda: RandomForestRegressor(cfg, engine="batched")
    base = _fit_with_threads(monkeypatch, ctor, X, y, 1)
    m = _fit_with_threads(monkeypatch, ctor, X, y, 4)
    _assert_ensembles_identical(base.ensemble, m.ensemble)


def test_native_threads_env_read_at_fit_time(monkeypatch):
    """REPRO_NATIVE_THREADS is re-read on every call (fit time), never
    cached at import time, and clamps to MAX_THREADS."""
    monkeypatch.delenv("REPRO_NATIVE_THREADS", raising=False)
    assert _native.native_threads() == 1
    monkeypatch.setenv("REPRO_NATIVE_THREADS", "3")
    assert _native.native_threads() == 3
    monkeypatch.setenv("REPRO_NATIVE_THREADS", " 8 ")
    assert _native.native_threads() == 8
    monkeypatch.setenv("REPRO_NATIVE_THREADS", str(10 * _native.MAX_THREADS))
    assert _native.native_threads() == _native.MAX_THREADS


@pytest.mark.parametrize("bad", ["0", "-2", "two", "1.5", ""])
def test_native_threads_invalid_falls_back_with_single_warning(
    monkeypatch, bad
):
    """Invalid REPRO_NATIVE_THREADS values (0, negatives, non-ints) fall
    back to 1 thread with exactly one RuntimeWarning per distinct value —
    mirroring the REPRO_TREE_ENGINE regression contract."""
    monkeypatch.setattr(_native, "_warned_threads", set())
    monkeypatch.setenv("REPRO_NATIVE_THREADS", bad)
    with pytest.warns(RuntimeWarning, match="REPRO_NATIVE_THREADS"):
        assert _native.native_threads() == 1
    import warnings as _w

    with _w.catch_warnings():
        _w.simplefilter("error")  # a second warning would raise
        assert _native.native_threads() == 1


@pytest.mark.skipif(not _native.available(), reason="native kernels unavailable")
def test_native_kernels_threaded_match_single_thread():
    """Direct kernel check: segment_sums / split_finder / partition produce
    byte-identical outputs at any thread count (not just via full fits)."""
    rng = np.random.default_rng(29)
    n, segs = 5000, 37
    vals = rng.normal(size=n)
    bounds = np.sort(rng.choice(np.arange(1, n), segs - 1, replace=False))
    starts = np.concatenate([[0], bounds]).astype(np.int64)
    counts = np.diff(np.concatenate([starts, [n]])).astype(np.int64)
    rows = np.arange(n, dtype=np.int64)
    outs = []
    for nt in (1, 2, 5):
        out = np.empty(segs)
        _native.segment_sums(vals, rows, starts, counts, out, nthreads=nt)
        outs.append(out)
    np.testing.assert_array_equal(outs[0], outs[1])
    np.testing.assert_array_equal(outs[0], outs[2])


# ------------------------------------------------------------ mega-grid recommend


def _fitted_predictor(model: str):
    from repro.core import FEATURE_NAMES, IOPerformancePredictor

    rng = np.random.default_rng(0)
    n = 240
    cols = {name: rng.uniform(1, 100, n) for name in FEATURE_NAMES}
    cols["target_throughput"] = (
        rng.uniform(10, 500, n) + 2.0 * cols[FEATURE_NAMES[0]]
    )
    return IOPerformancePredictor(model=model).fit(cols)


def _topk_key(recs):
    return [tuple(sorted((k, v) for k, v in r.items()
                         if k != "predicted_throughput_mb_s")) for r in recs]


@pytest.mark.parametrize("model", ["xgboost", "random_forest"])
def test_recommend_chunked_matches_oracle_paper_grid(model):
    """The chunked packed-ensemble scorer picks the identical top-k (and
    reports identical values) to the numpy oracle on the paper's 1,800-config
    grid, for both ensemble models."""
    from repro.core import ConfigSpace, recommend

    pred = _fitted_predictor(model)
    ctx = {"throughput_mb_s": 800.0, "file_size_mb": 64.0, "iops": 5e4}
    space = ConfigSpace()
    r_o = recommend(pred, ctx, space, top_k=5, scorer="oracle")
    r_c = recommend(pred, ctx, space, top_k=5, scorer="chunked")
    assert _topk_key(r_o) == _topk_key(r_c)
    for a, b in zip(r_o, r_c):
        assert a["predicted_throughput_mb_s"] == pytest.approx(
            b["predicted_throughput_mb_s"], rel=0, abs=0
        )


def test_recommend_pallas_kernel_matches_oracle_paper_grid(monkeypatch):
    """The Pallas one-hot-matmul kernel and the numpy oracle pick the
    identical top-k on the paper 1,800-config grid.  The program compiles
    the kernel; off the chip this test runs it in the Pallas interpreter."""
    import functools

    from repro.core import ConfigSpace, recommend
    from repro.kernels import ops

    monkeypatch.setattr(ops, "gbt_predict_op",
                        functools.partial(ops.gbt_predict_op, interpret=True))
    pred = _fitted_predictor("xgboost")
    ctx = {"throughput_mb_s": 800.0, "file_size_mb": 64.0, "iops": 5e4}
    space = ConfigSpace()
    r_o = recommend(pred, ctx, space, top_k=5, scorer="oracle")
    r_p = recommend(pred, ctx, space, top_k=5, scorer="pallas")
    assert _topk_key(r_o) == _topk_key(r_p)
    for a, b in zip(r_o, r_p):
        assert a["predicted_throughput_mb_s"] == b["predicted_throughput_mb_s"]


def test_recommend_auto_routes_and_falls_back():
    """scorer="auto" keeps small grids and non-ensemble models on the oracle
    path, routes mega grids through the chunked scorer, and forcing the
    packed scorers on a linear model falls back instead of crashing."""
    from repro.core import ConfigSpace, recommend
    from repro.core.autotune import MEGA_GRID_MIN, score_grid

    ctx = {"throughput_mb_s": 800.0, "file_size_mb": 64.0}
    small = ConfigSpace()
    assert small.n_candidates < MEGA_GRID_MIN
    mega = ConfigSpace(prefetch_policy=(0, 1), lookahead_batches=(4, 8),
                       cache_budget_mb=(32.0, 64.0))  # 1800 * 8 = 14400
    assert mega.n_candidates >= MEGA_GRID_MIN
    pred = _fitted_predictor("xgboost")
    assert score_grid(pred, ctx, small)[1] == "oracle"
    assert score_grid(pred, ctx, mega)[1] in ("chunked", "pallas")
    r_a = recommend(pred, ctx, mega, top_k=4)
    r_o = recommend(pred, ctx, mega, top_k=4, scorer="oracle")
    assert _topk_key(r_a) == _topk_key(r_o)
    lin = _fitted_predictor("linear")
    assert score_grid(lin, ctx, mega)[1] == "oracle"
    assert len(recommend(lin, ctx, small, top_k=3, scorer="pallas")) == 3
    with pytest.raises(ValueError, match="unknown scorer"):
        recommend(pred, ctx, small, scorer="warp")


def test_segment_sums_fast_matches_loop():
    from repro.core.tree import _segment_sums_fast, _segment_sums_loop

    rng = np.random.default_rng(17)
    lens = np.asarray(
        list(range(0, 132)) + [200, 1000, 8192, 8193, 20000], np.int64
    )
    vals = rng.normal(size=int(lens.sum()))
    vals *= 10.0 ** rng.integers(-8, 8, size=vals.size)
    starts = np.concatenate([[0], np.cumsum(lens)[:-1]])
    a = np.empty(lens.size)
    b = np.empty(lens.size)
    _segment_sums_loop(vals, starts, lens, a)
    _segment_sums_fast(vals, starts, lens, b)
    # The vectorized emulation either matches this numpy build bit-for-bit
    # (and then the engine may use it) or the runtime probe must say no.
    from repro.core.tree import _pairwise_emulation_ok

    assert np.array_equal(a, b) == _pairwise_emulation_ok() or np.array_equal(a, b)
