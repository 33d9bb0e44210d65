"""What decides ``correct``, at sizes a CPU test run holds: sound runs pass,
the bfloat16 control fails, and a run whose timed path alters an answer
where it is produced fails.  Each drives the whole run of a cell except the
harness's look for a chip."""

import numpy as np
import pytest

from chipbench import reference
from chipbench import run as harness

CPU = {"platform": "cpu", "kind": "cpu", "count": 1, "memory_peak_bytes": 0}


def _cell(name, bench):
    cell = harness.resolve(name, bench=bench)
    if name.startswith("serve"):
        cell.traffic = dict(cell.traffic, rate_rps=60.0, warmup_s=0.5)
    else:
        # a 27,000-candidate grid: still over MEGA_GRID_MIN, so chunked
        g = dict(cell.config["grid_mega"])
        for k in ("batch_size", "num_workers", "n_threads"):
            g[k] = g[k][:3]
        cell.config = dict(cell.config, grid_mega=g)
    return cell


def _run(name, bench, seed=2**31 + 11, control=False):
    cell = _cell(name, bench)
    record = cell.driver.run(cell, harness.Run(seed=seed, seconds=1.5, trace=False,
                                                control=control))
    return cell, record, harness.finish(cell, record, False, CPU)


@pytest.mark.parametrize("name", ["serve-paper-steady", "recommend-mega-1e6"])
def test_sound_run_is_correct_and_the_control_is_not(name, bench):
    cell, record, result = _run(name, bench, control=True)
    assert result["correct"], result["checks"]
    assert result["attempted"] > 0 and result["failed"] == 0
    limits = cell.traffic["limits"]
    control = record["control_checks"]
    assert any(v > limits[k] for k, v in control.items()), control
    # every number compared reaches the result line, with its limit
    assert set(result["checks"]) >= set(control)
    assert list(result)[-1] == "checks"


def test_serve_answer_altered_where_produced_is_not_correct(monkeypatch, bench):
    from repro.core.predictor import PredictorSnapshot

    real = PredictorSnapshot.predict_throughput_batch

    def altered(self, X):
        out = np.array(real(self, X), np.float64)
        out[0] *= 1.001
        return out

    monkeypatch.setattr(PredictorSnapshot, "predict_throughput_batch", altered)
    _, _, result = _run("serve-paper-steady", bench)
    assert not result["correct"]
    assert result["checks"]["predict_rel_err"]["value"] > 1e-4


def test_mega_answer_altered_where_produced_is_not_correct(monkeypatch, bench):
    import repro.core.autotune as autotune

    real = autotune.predict_ensemble

    def altered(ens, X):  # the chunk's first row scored far above the rest
        return real(ens, X).at[0].add(10.0)

    monkeypatch.setattr(autotune, "predict_ensemble", altered)
    _, _, result = _run("recommend-mega-1e6", bench)
    assert not result["correct"]
    assert result["checks"]["topk_gap"]["value"] > 1e-5


def test_mega_kernel_scores_in_bfloat16_are_not_correct(monkeypatch, bench):
    """A kernel that scores in bfloat16 while the re-scored top-k values
    stay exact: only the kernel's own scores can show it."""
    import jax.numpy as jnp

    import repro.core.autotune as autotune

    real = autotune.predict_ensemble

    def rounded(ens, X):
        return real(ens, X).astype(jnp.bfloat16).astype(jnp.float32)

    monkeypatch.setattr(autotune, "predict_ensemble", rounded)
    _, _, result = _run("recommend-mega-1e6", bench)
    assert not result["correct"]
    assert result["checks"]["kernel_score_err"]["value"] > 1e-4


def test_reference_agrees_with_the_program_oracle_and_not_in_bfloat16():
    from repro.core.ensemble_base import predict_ensemble_np

    from chipbench.drivers.recommend import fit_predictor

    cell = harness.resolve("recommend-mega-1e6")
    pred, errors = fit_predictor(cell.config, 3)
    assert not errors
    ens = reference.Ensemble.of(pred.model.ensemble)
    X = np.random.default_rng(0).choice([0, 1, 4, 16, 64, 256], size=(512, 11)).astype(float)
    want = predict_ensemble_np(pred.model.ensemble, X)
    assert np.allclose(reference.scores(ens, X), want, rtol=0, atol=1e-12)
    assert np.max(np.abs(reference.scores(ens, X, "bfloat16") - want)) > 1e-3


def test_fitted_rankings_depend_on_the_context():
    from chipbench.drivers.recommend import fit_predictor

    cell = harness.resolve("recommend-mega-1e6")
    pred, _ = fit_predictor(cell.config, 9)
    ens = reference.Ensemble.of(pred.model.ensemble)
    grid = reference.Grid(ens, cell.config["feature_names"], cell.config["grid_paper"])
    best = {int(np.argmax(grid.scores({"file_size_mb": fs, "throughput_mb_s": 500.0})))
            for fs in (1.0, 64.0, 4096.0)}
    assert len(best) > 1


def test_grid_scores_broadcast_equals_the_whole_grid():
    from chipbench import schedule
    from chipbench.drivers.recommend import fit_predictor

    cell = harness.resolve("recommend-mega-1e6")
    pred, _ = fit_predictor(cell.config, 5)
    ens = reference.Ensemble.of(pred.model.ensemble)
    names = cell.config["feature_names"]
    knobs = {k: v[:4] for k, v in cell.config["grid_mega"].items()}
    ctx = {"file_size_mb": 64.0, "throughput_mb_s": 500.0}
    n = int(np.prod([len(v) for v in knobs.values()]))
    X = np.stack([reference.row(names, {**ctx, **schedule.grid_candidate(knobs, i)})
                  for i in range(n)])
    assert np.array_equal(reference.grid_scores(ens, names, knobs, ctx),
                          reference.scores(ens, X))
