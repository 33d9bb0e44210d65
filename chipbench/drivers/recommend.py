"""Driver of the mega-grid cells: one caller, back to back, ``recommend()``
over the configuration's 10^6-candidate grid.

Set-up fits the configuration's predictor from the seed (the paper's
11-feature ``IOPerformancePredictor``), builds the grid and runs warm-up
calls, which compile the kernel's chunk shapes and fill the grid's cached
knob columns.  The window makes calls until ``--seconds`` have passed; it
lasts from the first call's start to the last call's end.  Each call's
duration is kept.  Then, for a seeded sample of the window's calls, each
call's top-k is compared with the float64 reference over the whole grid.
The top-k's
reported values are re-scored by the gather path, so the kernel's own log
scores are read too: ``score_grid`` with the same predictor, grid and chunk
shapes in each sampled context, compared with the reference over every
candidate.
"""

from __future__ import annotations

import time
from typing import Dict, List

import numpy as np

from chipbench import observations, reference, schedule
from chipbench.drivers import check_fitted
from chipbench.work.descent import descent_work

SPAN = "chipbench.recommend"


def compare(grid: reference.Grid, calls: List[tuple], top_k: int,
            kernel: List[tuple]) -> Dict[str, float]:
    """Readings of the calls' top-k (``(context, top)`` pairs) and of the
    kernel's grid scores (``(context, log scores)`` pairs)."""
    out = {"topk_gap": 0.0, "topk_rel_err": 0.0, "kernel_score_err": 0.0}
    grid.walk([ctx for ctx, _ in calls] + [ctx for ctx, _ in kernel])
    for ctx, top in calls:
        r = grid.readings(ctx, top, top_k)
        out["topk_gap"] = max(out["topk_gap"], r["gap"])
        out["topk_rel_err"] = max(out["topk_rel_err"], r["rel_err"])
    for ctx, got in kernel:
        err = np.abs(np.asarray(got, np.float64) - grid.scores(ctx))
        out["kernel_score_err"] = max(out["kernel_score_err"],
                                      float(np.max(err)) if np.all(np.isfinite(err))
                                      else np.inf)
    return out


def fit_predictor(cfg: dict, seed: int):
    from repro.core import IOPerformancePredictor
    from repro.core.features import TARGET_NAME

    obs = cfg["observations"]
    rows = observations.observations(cfg["grid_paper"], obs["axes"], obs["repeats"], seed)
    cols = {n: np.asarray([float(r.get(n, 0.0)) for r in rows])
            for n in tuple(cfg["feature_names"]) + (TARGET_NAME,)}
    pred = IOPerformancePredictor(model=cfg["model"]["name"], seed=seed).fit(cols)
    errors = check_fitted(cfg, cfg["feature_names"], pred.spec.names, pred.model)
    return pred, errors


def run(cell, run) -> dict:
    import jax

    from repro.core import ConfigSpace
    from repro.core.autotune import KNOB_NAMES, recommend, score_grid

    cfg, traffic = cell.config, cell.traffic
    k = traffic["top_k"]
    pred, errors = fit_predictor(cfg, run.seed)
    ens = reference.Ensemble.of(pred.model.ensemble)
    grid = reference.Grid(ens, cfg["feature_names"], cfg["grid_mega"])
    space = ConfigSpace(**cfg["grid_mega"])
    if tuple(cfg["grid_mega"]) != tuple(KNOB_NAMES):
        errors.append(f"grid knobs {tuple(cfg['grid_mega'])} are not in the program's "
                      f"order {tuple(KNOB_NAMES)}")
    contexts = schedule.log_uniform_contexts(traffic["contexts"], traffic["n_contexts"],
                                             run.seed)
    warm = schedule.log_uniform_contexts(traffic["contexts"], traffic["warmup_calls"],
                                         run.seed + 1)
    for ctx in warm:
        recommend(pred, ctx, space, top_k=k)

    tr = traffic["trace"]
    calls = []
    call_s = []
    trace = None
    traced_calls = 0
    t_start = t = time.monotonic()
    while t - t_start < run.seconds or (run.trace and trace is None):
        if run.trace and len(calls) == tr["after_calls"]:
            run.tracer.start()
        ctx = contexts[len(calls) % len(contexts)]
        t_call = time.monotonic()
        with jax.profiler.TraceAnnotation(SPAN):
            top = recommend(pred, ctx, space, top_k=k)
        t = time.monotonic()
        call_s.append(t - t_call)
        calls.append((ctx, top))
        if run.trace and len(calls) == tr["after_calls"] + tr["calls"]:
            trace = run.tracer.stop()
            traced_calls = tr["calls"]
            t = time.monotonic()
    window_s = t - t_start
    memory_peak = run.memory_peak_bytes()
    want_mode = "pallas" if jax.devices()[0].platform == "tpu" else "chunked"
    sample = [calls[i] for i in schedule.rng(run.seed, "check-sample")
              .permutation(len(calls))[:traffic["check_sample"]]]
    kernel = []
    for ctx, _ in sample:
        scores, mode = score_grid(pred, ctx, space)
        if mode != want_mode:
            errors.append(f"score_grid ran {mode!r}, not {want_mode!r}")
        kernel.append((ctx, scores))
    del pred

    readings = compare(grid, sample, k, kernel)
    limits = traffic["limits"]
    ops, nbytes = descent_work(space.n_candidates, len(cfg["feature_names"]),
                               ens.feature.shape[0], ens.max_depth, ens.real_nodes())
    record = {
        "setup_s": t_start - run.t0,
        "window_s": window_s,
        "attempted": len(calls),
        "failed": 0,
        "memory_peak_bytes": memory_peak,
        "errors": errors,
        "candidates": len(calls) * space.n_candidates,
        "call_s": call_s,
        "device_kind": jax.devices()[0].device_kind,
        "work_per_call": {"ops": ops, "bytes": nbytes},
        "checks": {n: {"value": float(v), "limit": float(limits[n])}
                   for n, v in readings.items()},
    }
    if trace is not None:
        record["trace"] = trace
        record["traced_calls"] = traced_calls
    if run.control:
        record["control_checks"] = compare(
            grid, [(ctx, grid.control_top(ctx, k)) for ctx, _ in sample], k,
            [(ctx, grid.scores(ctx, "bfloat16")) for ctx, _ in kernel])
    return record
