"""Driver of the HTTP serving cells: ``/predict`` and ``/recommend`` sent in
an open loop to ``RecommendationService``.

Set-up fits the configuration's predictor from the seed's observations
(``chipbench/observations.py``) inside an ``OnlineAutotuner`` (as
``python -m repro.service.serve`` does), starts the
service with the configuration's ``ServeConfig``, compiles every descent
shape the batcher can send (the power-of-two predict buckets and the grid),
and starts the load generator in a child process that never imports JAX.
The generator first sends warm-up traffic from tenants the window never
uses, then the window's schedule.  After the window it waits for every
answer, and every ``200`` answer is compared with the float64 reference.
"""

from __future__ import annotations

import hashlib
import json
import math
import os
import pathlib
import subprocess
import sys
import time
from typing import Dict, List

import numpy as np

from chipbench import observations, reference, schedule
from chipbench.drivers import check_fitted

LOADGEN = pathlib.Path(__file__).resolve().parent.parent / "loadgen.py"


def _sleep_until(t: float) -> None:
    d = t - time.monotonic()
    if d > 0:
        time.sleep(d)


def _stats(service) -> dict:
    status, body = service.handle("GET", "/stats", b"")
    if status != 200:
        raise RuntimeError(f"/stats answered {status}")
    return json.loads(body)


def stats_delta(a: dict, b: dict) -> Dict[str, int]:
    """Counter deltas between two ``/stats`` readings."""
    def get(s, *path):
        for p in path:
            s = s.get(p, 0) if isinstance(s, dict) else 0
        return int(s or 0)

    keys = {"predict": ("requests", "/predict"), "recommend": ("requests", "/recommend"),
            "errors": ("errors",), "shed": ("admission", "shed"),
            "deadline_timeouts": ("admission", "deadline_timeouts"),
            "hits": ("cache", "hits"), "misses": ("cache", "misses"),
            "n_batches": ("batching", "n_batches"), "n_scored": ("batching", "n_scored")}
    return {k: get(b, *p) - get(a, *p) for k, p in keys.items()}


def hash_seed(s: str, k: int) -> int:
    """A stable integer from a label and the run's seed (no hash() salt)."""
    return int.from_bytes(hashlib.sha256(f"{s}/{k}".encode()).digest()[:8], "little")


def _axes(spec: dict) -> Dict[str, List[float]]:
    return {k: [round(float(v), 3) for v in np.geomspace(lo, hi, int(n))]
            for k, (lo, hi, n) in spec.items()}


def build_requests(traffic: dict, knobs: dict, seconds: float, seed: int) -> List[list]:
    """``[phase, offset_s, path, body]`` for the warm-up and the window."""
    window_tenants, warm_tenants = schedule.tenant_contexts(
        _axes(traffic["tenant_axes"]), traffic["tenants"], traffic["warmup_tenants"], seed)
    n_cands = int(np.prod([len(v) for v in knobs.values()]))
    out: List[list] = []
    for phase, secs, tenants in (("warmup", traffic["warmup_s"], warm_tenants),
                                 ("window", seconds, window_tenants)):
        s = f"{seed}/{phase}"
        times = schedule.poisson_arrivals(traffic["rate_rps"], secs, hash_seed(s, 0))
        n = len(times)
        kinds = schedule.mix(n, traffic["mix"], hash_seed(s, 1))
        ranks = schedule.zipf_ranks(n, len(tenants), traffic["zipf_s"], hash_seed(s, 2))
        n_pred = kinds.count("predict")
        cand = iter(schedule.uniform_indices(n_pred, n_cands, hash_seed(s, 3), "configs"))
        for t, kind, r in zip(times, kinds, ranks):
            ctx = tenants[int(r)]
            if kind == "predict":
                body = {"context": ctx,
                        "config": schedule.grid_candidate(knobs, int(next(cand)))}
            else:
                body = {"context": ctx, "top_k": traffic["top_k"]}
            out.append([phase, float(t), f"/{kind}", json.dumps(body)])
    return out


def _warm_shapes(snap, service, n_features: int, max_batch: int, warm_ctx: dict,
                 top_k: int) -> None:
    """Compile every shape the window can send to the device."""
    from repro.core.autotune import recommend

    b = 1
    while b <= max_batch:
        snap.predict_throughput_batch(np.ones((b, n_features)))
        b *= 2
    recommend(snap, service.tuner.filter_context(warm_ctx), service.space, top_k=top_k)


class Reference:
    """The float64 reference (and the bfloat16 control) of one fitted model,
    featurising as the online tuner does: the varied knobs and the context
    keys the configuration names, every other feature 0."""

    def __init__(self, ens: reference.Ensemble, cfg: dict):
        knobs = cfg["grid_paper"]
        self.ens, self.names = ens, cfg["online_feature_names"]
        self.grid = reference.Grid(ens, self.names, knobs)
        self.keep = ({k for k, v in knobs.items() if len(v) > 1}
                     | set(cfg["online_context_keys"]))

    def filtered(self, context: dict, config: dict = None) -> dict:
        out = {k: float(v) for k, v in context.items() if k in self.keep}
        out.update({k: float(v) for k, v in (config or {}).items() if k in self.keep})
        return out

    def predict_log(self, bodies: List[dict], precision: str) -> np.ndarray:
        X = np.stack([reference.row(self.names, self.filtered(b["context"], b["config"]))
                      for b in bodies]) if bodies else np.zeros((0, len(self.names)))
        return reference.scores(self.ens, X, precision)


def compare(ref: Reference, answers: List[tuple], top_k: int) -> Dict[str, float]:
    """Readings of served answers against the float64 reference.
    ``answers``: (kind, request body, response body), 200s only."""
    preds = [(q, a) for kind, q, a in answers if kind == "predict"]
    want = np.expm1(ref.predict_log([q for q, _ in preds], "float64"))
    got = np.asarray([a.get("predicted_throughput_mb_s", np.nan) for _, a in preds], np.float64)
    rel = np.abs(got - want) / np.abs(want)
    out = {"predict_rel_err": float(np.max(rel, initial=0.0)) if np.all(np.isfinite(rel)) else 1.0,
           "recommend_gap": 0.0, "recommend_rel_err": 0.0}
    ref.grid.walk([ref.filtered(q["context"]) for kind, q, _ in answers
                   if kind == "recommend"])
    for kind, q, a in answers:
        if kind == "recommend":
            r = ref.grid.readings(ref.filtered(q["context"]), a.get("top", []), top_k)
            out["recommend_gap"] = max(out["recommend_gap"], r["gap"])
            out["recommend_rel_err"] = max(out["recommend_rel_err"], r["rel_err"])
    return out


def control_answers(ref: Reference, answers: List[tuple], top_k: int) -> List[tuple]:
    """The answers the bfloat16 control gives to the same requests."""
    preds = [q for kind, q, _ in answers if kind == "predict"]
    ref.grid.walk([ref.filtered(q["context"]) for kind, q, _ in answers
                   if kind == "recommend"], "bfloat16")
    vals = iter(np.expm1(ref.predict_log(preds, "bfloat16")))
    return [(kind, q, {"predicted_throughput_mb_s": float(next(vals))} if kind == "predict"
             else {"top": ref.grid.control_top(ref.filtered(q["context"]), top_k)})
            for kind, q, _ in answers]


def p95(values: List[float]) -> float:
    """Nearest-rank 95th percentile."""
    xs = sorted(values)
    return xs[max(0, math.ceil(0.95 * len(xs)) - 1)] if xs else float("nan")


def run(cell, run) -> dict:
    from repro.core.autotune import ConfigSpace, OnlineAutotuner
    from repro.service.serve import RecommendationService, ServeConfig

    cfg, traffic = cell.config, cell.traffic
    seed = run.seed
    space = ConfigSpace(**cfg["grid_paper"])
    tuner = OnlineAutotuner(space=space, model=cfg["model"]["name"], seed=seed)
    obs = cfg["observations"]
    tuner.seed_observations(observations.observations(cfg["grid_paper"], obs["axes"],
                                                      obs["repeats"], seed))
    tuner.maybe_refit()
    errors = check_fitted(cfg, cfg["online_feature_names"], tuner.spec.names,
                          tuner.predictor.model)
    snap = tuner.snapshot()
    ref = Reference(reference.Ensemble.of(snap.model.ensemble), cfg)
    service = RecommendationService(tuner, ServeConfig(port=0, **cfg["serve"]))
    service.start()
    reqs = build_requests(traffic, cfg["grid_paper"], run.seconds, seed)
    proc = None
    try:
        warm_ctx = json.loads(next(r for r in reqs if r[0] == "warmup")[3])["context"]
        _warm_shapes(snap, service, len(ref.names), cfg["serve"]["max_batch"], warm_ctx,
                     traffic["top_k"])
        proc = subprocess.Popen(
            [sys.executable, str(LOADGEN)], stdin=subprocess.PIPE, stdout=subprocess.PIPE,
            text=True, env=dict(os.environ, JAX_PLATFORMS="cpu"))
        proc.stdin.write(json.dumps({"port": service.port,
                                     "connections": traffic["connections"],
                                     "timeout_s": traffic["timeout_s"],
                                     "requests": reqs}) + "\n")
        proc.stdin.flush()
        if proc.stdout.readline().strip() != "ready":
            raise RuntimeError("load generator did not start")
        t_warm = time.monotonic() + 0.2
        t_win = t_warm + traffic["warmup_s"]
        t_end = t_win + run.seconds
        proc.stdin.write(json.dumps({"warmup": t_warm, "window": t_win}) + "\n")
        proc.stdin.flush()
        _sleep_until(t_win)
        s0 = _stats(service)
        trace = slice_stats = None
        if run.trace:
            tr = traffic["trace"]
            _sleep_until(t_win + tr["offset_s"])
            run.tracer.start()
            a = _stats(service)
            _sleep_until(time.monotonic() + tr["seconds"])
            b = _stats(service)
            trace = run.tracer.stop()
            slice_stats = stats_delta(a, b)
        _sleep_until(t_end)
        s1 = _stats(service)
        out, _ = proc.communicate(timeout=traffic["timeout_s"] + run.seconds + 30)
        proc = None
        records = json.loads(out)["records"]
    finally:
        if proc is not None:
            proc.kill()
            proc.wait()
        service.shutdown()
    memory_peak = run.memory_peak_bytes()
    del snap, tuner, service

    window = [(reqs[r[0]], r) for r in records if reqs[r[0]][0] == "window"]
    lat, lag, answers = [], [], []
    completed = unanswered = server_errors = failed = 0
    for (phase, _, path, body), (_, due, sent, done, status, resp) in window:
        if sent is not None:
            lag.append(sent - due)
        if status == 200:
            try:
                answers.append((path.strip("/"), json.loads(body), json.loads(resp)))
                lat.append(done - due)
                completed += done <= t_end
                continue
            except json.JSONDecodeError:
                status = 500  # an answer that is not JSON says the wrong thing
        failed += 1
        lat.append(math.inf)
        if status is None or status == 0:
            unanswered += 1
        elif status == 500:
            server_errors += 1
    readings = compare(ref, answers, traffic["top_k"])
    readings.update(unanswered=unanswered, server_errors=server_errors)
    limits = traffic["limits"]
    record = {
        "setup_s": t_win - run.t0,
        "window_s": run.seconds,
        "attempted": len(window),
        "failed": failed,
        "memory_peak_bytes": memory_peak,
        "errors": errors,
        "completed_200": completed,
        "latency_p95_s": p95(lat),
        "latencies_s": lat,
        "lag_p95_s": p95(lag),
        "stats": stats_delta(s0, s1),
        "checks": {k: {"value": float(v), "limit": float(limits[k])}
                   for k, v in readings.items()},
    }
    if trace is not None:
        record["trace"] = trace
        record["slice_stats"] = slice_stats
    if run.control:
        record["control_checks"] = compare(ref, control_answers(ref, answers,
                                                                traffic["top_k"]),
                                           traffic["top_k"])
    return record
