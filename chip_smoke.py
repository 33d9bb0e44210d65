"""One-chip smoke test of the main path, through the entry points users call.

    python chip_smoke.py [--seed N]

Run from the repository root on a machine with one TPU.  Everything runs in
this one process (the HTTP server and its batcher are threads), so the chip
has a single owner.  Phases, each printing what it checked:

0. setup: the compile cache, then a TPU is required (no CPU fallback); the
   device, its memory limit, and two host facts: whether the native tree
   kernels loaded and the free bytes of the two storage tiers.
1. predictors: the paper's ``xgboost`` and ``random_forest`` at paper size,
   fitted on seeded synthetic observations.
2. serving: ``RecommendationService`` over HTTP on the paper's 1,800-candidate
   grid; ``/predict`` and ``/recommend`` agree with the numpy oracle.
3. mega grid: ``recommend()`` over 10^6 candidates through the compiled Pallas
   kernel, for both predictors; scores and top 5 agree with the numpy oracle.
4. training: ``Trainer`` steps on granite-moe-1b-a400m at its published
   widths, depth cut to what one chip holds, fed by a ``DataPipeline`` over a
   packed token dataset on the tmpfs tier.

The last line of stdout, printed only when every phase passed, is one JSON
object naming the device.  Any failure exits non-zero.
"""

from __future__ import annotations

import argparse
import hashlib
import http.client
import json
import math
import pathlib
import shutil
import sys
import threading
import traceback

ROOT = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))
OUT_DIR = ROOT / ".chip_smoke"

# Phase 4 cut of granite-moe-1b-a400m: published widths, 16 of its 24 layers.
# The train state (bf16 weights, f32 Adam moments) is 10 B per parameter, and
# the step's bf16 gradients bring it to 12 B; all 24 layers (1.34 B
# parameters) would not fit in 16 GB.  On a v5e the step holds the state in
# bytes_in_use and its temporaries in bytes_reserved (memory_analysis reads the
# temporaries about 1.5x too high).  At 4 x 2048 tokens the two together take
# 14.3 GB of the 16.9 GB limit at 16 layers, and grow by about 0.7 GB a layer,
# so 16 is the deepest cut that leaves a tenth of the chip free.  Phase 4
# prints both readings for the executable it ran.
TRAIN_ARCH = "granite-moe-1b-a400m"
TRAIN_LAYERS = 16
TRAIN_BATCH = 4
TRAIN_SEQ = 2048
TRAIN_STEPS = 6          # step 1 compiles; autotune ticks after steps 3 and 6
TRAIN_AUTOTUNE_EVERY = 3
TRAIN_RECORDS = 64
FIRST_LOSS_TOL = 0.25    # first loss vs ln(vocab) at this init

# float32 tolerance of the device paths against the float64 numpy oracle
LOG_ATOL = 1e-5          # ensemble outputs in log1p(MB/s) space
MBS_RTOL = 1e-5          # predicted MB/s after expm1


class Checks:
    def __init__(self):
        self.failed = []

    def __call__(self, phase: str, name: str, ok: bool, detail: str = "") -> bool:
        print(f"[{phase}] {name}: {'ok' if ok else 'FAIL'}"
              + (f" ({detail})" if detail else ""), flush=True)
        if not ok:
            self.failed.append(f"{phase}/{name}")
        return ok


def _tier_free_bytes(path: pathlib.Path) -> int:
    while not path.exists():
        path = path.parent
    return shutil.disk_usage(path).free


def _oracle_log(ens, X):
    """float64 numpy oracle over the distinct rows of ``X`` (grids repeat a
    row for every knob the feature spec does not hold)."""
    import numpy as np

    from repro.core.ensemble_base import predict_ensemble_np

    uniq, inv = np.unique(X, axis=0, return_inverse=True)
    return predict_ensemble_np(ens, uniq)[inv.reshape(-1)]


def _top_k_matches(ens, spec, grid_scores, context, picks, k):
    """True when ``picks`` (candidate dicts) are a top ``k`` of the oracle's
    ``grid_scores``: the oracle scores of the picks equal the ``k`` best
    oracle scores.  Ties at the k-th score make any of the tied candidates a
    correct pick, so candidates are compared by score, not by index."""
    import numpy as np

    from repro.core.ensemble_base import predict_ensemble_np

    rows = np.stack([spec.row({**context, **c}) for c in picks])
    got = np.sort(predict_ensemble_np(ens, rows))[::-1]
    best = np.sort(grid_scores)[::-1][:k]
    kth = best[-1]
    n_tied = int(np.sum(np.abs(grid_scores - kth) <= LOG_ATOL))
    ok = len(picks) == k and np.allclose(got, best, rtol=0.0, atol=LOG_ATOL)
    return ok, best, n_tied


def phase_setup(check):
    from repro.compile_cache import use_compile_cache

    cache = use_compile_cache()
    import jax

    devices = jax.devices()
    dev = devices[0]
    print(f"[setup] compile cache: {cache}", flush=True)
    if dev.platform != "tpu":
        check("setup", "tpu", False, f"JAX found {dev.platform}, not a TPU")
        return None
    stats = dev.memory_stats() or {}
    print(f"[setup] device: {dev.device_kind}, count {len(devices)}, "
          f"bytes_limit {stats.get('bytes_limit')}", flush=True)

    from repro.core import _native
    from repro.data.storage import _default_roots

    print(f"[setup] native tree kernels loaded: {_native.available()}", flush=True)
    for tier, root in zip(("disk", "tmpfs"), _default_roots()):
        print(f"[setup] storage tier {tier} {root}: "
              f"{_tier_free_bytes(root)} bytes free", flush=True)
    return dev, len(devices)


def phase_predictors(check, seed):
    import numpy as np

    from repro.core import FEATURE_NAMES, ConfigSpace, IOPerformancePredictor
    from repro.core.ensemble_base import predict_ensemble_np
    from repro.core.features import TARGET_NAME
    from repro.service.serve import synthetic_observations

    rows = synthetic_observations(ConfigSpace(), seed=seed)
    cols = {name: np.asarray([float(r.get(name, 0.0)) for r in rows])
            for name in FEATURE_NAMES + (TARGET_NAME,)}
    print(f"[fit] observations: synthetic_observations(ConfigSpace(), "
          f"seed={seed}), {len(rows)} rows", flush=True)
    predictors = {}
    for name in ("xgboost", "random_forest"):
        pred = IOPerformancePredictor(model=name, seed=seed).fit(cols)
        ens = pred.model.ensemble
        X = pred.spec.matrix(cols)[:512]
        dev_log = pred.predict_log(X)
        err = float(np.max(np.abs(dev_log - predict_ensemble_np(ens, X))))
        check("fit", name, bool(np.all(np.isfinite(dev_log))) and err <= LOG_ATOL,
              f"{ens.n_trees} trees, max_depth {ens.max_depth}, "
              f"{ens.feature.shape[1]} nodes per tree; device descent vs "
              f"oracle max |diff| {err:.3g} on 512 rows")
        predictors[name] = pred
    return predictors


def _http(port, method, path, payload=None):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=300)
    try:
        body = json.dumps(payload).encode() if payload is not None else None
        conn.request(method, path, body=body,
                     headers={"Content-Type": "application/json"} if body else {})
        resp = conn.getresponse()
        return resp.status, json.loads(resp.read())
    finally:
        conn.close()


def phase_serving(check, seed):
    import numpy as np

    from repro.core import ConfigSpace, OnlineAutotuner
    from repro.core.ensemble_base import predict_ensemble_np
    from repro.core.features import expm1_inverse
    from repro.service.serve import (
        RecommendationService, ServeConfig, synthetic_observations,
    )

    space = ConfigSpace()
    tuner = OnlineAutotuner(space=space, model="xgboost", seed=seed)
    tuner.seed_observations(synthetic_observations(space, seed=seed))
    tuner.maybe_refit()
    snap = tuner.snapshot()
    ens = snap.model.ensemble
    cfg = ServeConfig(port=0, batching=True, batch_window_ms=20.0)
    service = RecommendationService(tuner, cfg,
                                    progress=lambda m: print(f"[serve] {m}", flush=True))
    service.start()
    port = service.port
    try:
        status, h = _http(port, "GET", "/healthz")
        check("serve", "healthz", status == 200 and h.get("fitted") is True,
              f"status {status}, {h.get('status')}, generation "
              f"{h.get('model_generation')}")

        ctx = {"file_size_mb": 64.0, "n_samples": 1000.0, "throughput_mb_s": 120.0}
        cands = space.candidates()
        configs = [cands[i] for i in range(7, len(cands), len(cands) // 16)][:16]
        results = [None] * len(configs)
        barrier = threading.Barrier(len(configs) - 2)

        def send(i, wait):
            if wait:
                barrier.wait()
            results[i] = _http(port, "POST", "/predict",
                               {"context": ctx, "config": configs[i]})

        for i in range(2):  # two alone, the rest all at once
            send(i, False)
        threads = [threading.Thread(target=send, args=(i, True))
                   for i in range(2, len(configs))]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        rows = np.stack([tuner.spec.row(tuner.filter_context(ctx, knobs=c))
                         for c in configs])
        want = expm1_inverse(predict_ensemble_np(ens, rows))
        got = np.asarray([r[1].get("predicted_throughput_mb_s", np.nan)
                          for r in results])
        statuses = sorted({r[0] for r in results})
        check("serve", "predict", statuses == [200]
              and np.allclose(got, want, rtol=MBS_RTOL, atol=0.0),
              f"{len(configs)} requests, statuses {statuses}, max rel diff vs "
              f"oracle {float(np.max(np.abs(got - want) / want)):.3g}")

        oracle_space = ConfigSpace()  # the service's grid cache is its own
        contexts = [ctx,
                    {"file_size_mb": 512.0, "n_samples": 20000.0},
                    {"file_size_mb": 4.0, "n_samples": 100.0, "iops": 5e4}]
        for j, c in enumerate(contexts):
            status, r = _http(port, "POST", "/recommend", {"context": c, "top_k": 5})
            top = r.get("top", []) if status == 200 else []
            filtered = tuner.filter_context(c)
            scores = _oracle_log(ens, oracle_space.feature_matrix(tuner.spec, filtered))
            picks = [{k: v for k, v in t.items() if k != "predicted_throughput_mb_s"}
                     for t in top]
            ok, best, n_tied = _top_k_matches(ens, tuner.spec, scores, filtered,
                                              picks, 5)
            vals = np.asarray([t["predicted_throughput_mb_s"] for t in top])
            ok = ok and np.allclose(vals, expm1_inverse(best), rtol=MBS_RTOL, atol=0.0)
            check("serve", f"recommend[{j}]", status == 200 and ok,
                  f"status {status}, top 5 of {space.n_candidates} equal the "
                  f"oracle's ({n_tied} candidates tie at the 5th score)")

        status, s = _http(port, "GET", "/stats")
        batching = s.get("batching", {})
        check("serve", "batched", status == 200 and batching.get("max_batch", 0) > 1,
              f"{batching.get('n_batches')} batches, largest "
              f"{batching.get('max_batch')} rows, errors {s.get('errors')}")
    finally:
        service.shutdown()


def phase_mega_grid(check, predictors):
    import numpy as np

    from benchmarks.fit_bench import mega_grid_spaces
    from repro.core import recommend
    from repro.core.autotune import MEGA_GRID_CHUNK, score_grid
    from repro.kernels.gbt_predict import gbt_predict

    space = mega_grid_spaces()["1e6"]
    ctx = {"throughput_mb_s": 800.0, "file_size_mb": 64.0, "iops": 5e4}
    for name, pred in predictors.items():
        ens = pred.model.ensemble
        spec = pred.spec
        hlo = gbt_predict.lower(
            np.zeros((MEGA_GRID_CHUNK, len(spec.names)), np.float32),
            ens.feature, ens.threshold, ens.left, ens.right, ens.value,
            max_depth=ens.max_depth, base_score=float(ens.base_score),
            scale=float(ens.scale)).as_text()
        scores, mode = score_grid(pred, ctx, space)
        check("mega", f"{name} scorer", mode == "pallas" and "tpu_custom_call" in hlo,
              f"mode {mode}, compiled Mosaic kernel: {'tpu_custom_call' in hlo}, "
              f"{space.n_candidates} candidates, {ens.feature.shape[1]} nodes per "
              f"tree ({-(-ens.feature.shape[1] // 128) * 128} padded), "
              f"{ens.n_trees} trees")
        oracle = _oracle_log(ens, space.feature_matrix(spec, ctx))
        err = float(np.max(np.abs(scores - oracle)))
        check("mega", f"{name} scores", bool(np.all(np.isfinite(scores)))
              and err <= LOG_ATOL,
              f"max |kernel - oracle| {err:.3g} over {scores.shape[0]} candidates")
        top = recommend(pred, ctx, space, top_k=5)
        picks = [{k: v for k, v in t.items() if k != "predicted_throughput_mb_s"}
                 for t in top]
        ok, _, n_tied = _top_k_matches(ens, spec, oracle, ctx, picks, 5)
        check("mega", f"{name} top5", ok,
              f"top 5 equal the oracle's ({n_tied} candidates tie at the 5th score)")


def phase_train(check, seed):
    import numpy as np

    from repro.configs import get_config
    from repro.data import (
        BACKENDS, DataPipeline, PipelineConfig, TokenRecordCodec, open_dataset,
        write_dataset,
    )
    from repro.data.storage import StorageBackend
    from repro.train.trainer import Trainer, TrainerConfig

    import jax

    cfg = get_config(TRAIN_ARCH)
    print(f"[train] {TRAIN_ARCH}: d_model {cfg.d_model}, {cfg.n_heads} query / "
          f"{cfg.n_kv_heads} KV heads of {cfg.head_dim}, {cfg.n_experts} experts "
          f"of d_ff {cfg.d_ff}, top-{cfg.top_k}, vocab {cfg.vocab_size}", flush=True)
    print(f"[train] cut: {TRAIN_LAYERS} of {cfg.n_layers} layers, batch "
          f"{TRAIN_BATCH} x sequence {TRAIN_SEQ}; the left-out layers stand for "
          f"further pipeline stages", flush=True)
    cfg = cfg.replace(n_layers=TRAIN_LAYERS)

    codec = TokenRecordCodec(TRAIN_SEQ + 1)
    tokens = np.random.default_rng(seed).integers(
        0, cfg.vocab_size, size=(TRAIN_RECORDS, TRAIN_SEQ + 1), dtype=np.int32)
    # a directory of this checkout's own on the tmpfs tier, removed at the end
    key = hashlib.sha256(str(ROOT).encode()).hexdigest()[:16]
    backend = StorageBackend("tmpfs", BACKENDS["tmpfs"].root / f"chip_smoke_{key}")
    manifest = write_dataset(backend, f"tokens_s{seed}",
                             [codec.encode(t) for t in tokens], "packed")
    reader = open_dataset(backend, manifest)
    pipe = DataPipeline.from_reader(
        reader, TRAIN_SEQ + 1,
        PipelineConfig(batch_size=TRAIN_BATCH, num_workers=2, seed=seed))
    print(f"[train] dataset: {TRAIN_RECORDS} packed records of "
          f"{codec.nbytes} bytes at {manifest['files'][0]}", flush=True)
    ckpt_dir = OUT_DIR / "ckpt"  # fresh: a checkpoint left here would resume
    shutil.rmtree(ckpt_dir, ignore_errors=True)
    tcfg = TrainerConfig(num_steps=TRAIN_STEPS, ckpt_every=TRAIN_STEPS + 1,
                         ckpt_dir=str(ckpt_dir), autotune_every=TRAIN_AUTOTUNE_EVERY,
                         log_every=1, seed=seed)
    trainer = Trainer(cfg, pipe, tcfg)
    try:
        out = trainer.run()
    finally:
        pipe.close()
        reader.close()
        backend.cleanup()
        shutil.rmtree(OUT_DIR, ignore_errors=True)
    hist = out["history"]
    check("train", "steps", out["final_step"] == TRAIN_STEPS
          and len(hist) == TRAIN_STEPS and all(math.isfinite(x) for x in hist),
          f"{len(hist)} steps, losses {[round(x, 4) for x in hist]}")
    # The footprint of the step executable the trainer ran (same program, so
    # the compile is a cache hit), beside what the device allocator saw.
    batch = trainer.make_batch(np.zeros((TRAIN_BATCH, TRAIN_SEQ + 1), np.int32))
    m = trainer._step.lower(out["state"], batch).compile().memory_analysis()
    print(f"[train] step memory_analysis: arguments {m.argument_size_in_bytes}, "
          f"outputs {m.output_size_in_bytes}, aliased {m.alias_size_in_bytes}, "
          f"temporaries {m.temp_size_in_bytes}, code "
          f"{m.generated_code_size_in_bytes} bytes", flush=True)
    stats = jax.devices()[0].memory_stats() or {}
    print(f"[train] memory_stats: {stats}", flush=True)
    print(f"[train] on the device: bytes_in_use + bytes_reserved "
          f"{stats.get('bytes_in_use', 0) + stats.get('bytes_reserved', 0)} of "
          f"bytes_limit {stats.get('bytes_limit')}", flush=True)
    ln_v = math.log(cfg.vocab_size)
    check("train", "first_loss", abs(hist[0] - ln_v) <= FIRST_LOSS_TOL,
          f"{hist[0]:.4f} vs ln({cfg.vocab_size}) = {ln_v:.4f}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the observations, weights and token data")
    args = ap.parse_args(argv)
    check = Checks()

    setup = phase_setup(check)
    if setup is None:
        return 1
    dev, count = setup

    def run(phase, fn, *fn_args):
        try:
            return fn(check, *fn_args)
        except Exception:  # noqa: BLE001 - a phase that raises is a failed check
            traceback.print_exc()
            check(phase, "raised", False)
            return None

    predictors = run("fit", phase_predictors, args.seed)
    run("serve", phase_serving, args.seed)
    if predictors:
        run("mega", phase_mega_grid, predictors)
    else:
        check("mega", "skipped", False, "no fitted predictors")
    run("train", phase_train, args.seed)

    if check.failed:
        print(f"[smoke] FAILED: {', '.join(check.failed)}", flush=True)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": count}}),
        flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
