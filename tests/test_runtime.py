"""Optimizer, checkpoint manager, trainer fault tolerance, serving engine."""

import json
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import CheckpointManager
from repro.optim import AdamWConfig, adamw_update, cosine_schedule


# ---------------------------------------------------------------- optim
def test_adamw_converges_quadratic():
    cfg = AdamWConfig(lr=0.1, weight_decay=0.0, clip_norm=None)
    p = {"w": jnp.array([5.0, -3.0])}
    mu = {"w": jnp.zeros(2)}
    nu = {"w": jnp.zeros(2)}
    for step in range(200):
        g = {"w": 2 * p["w"]}  # grad of ||w||^2
        p, mu, nu, _ = adamw_update(g, p, mu, nu, jnp.int32(step), cfg)
    assert float(jnp.abs(p["w"]).max()) < 1e-2


def test_grad_clip_bounds_norm():
    cfg = AdamWConfig(clip_norm=1.0)
    g = {"w": jnp.full((4,), 100.0)}
    p = {"w": jnp.zeros(4)}
    mu = {"w": jnp.zeros(4)}
    nu = {"w": jnp.zeros(4)}
    _, mu2, _, m = adamw_update(g, p, mu, nu, jnp.int32(0), cfg)
    assert m["grad_norm"] > 100  # pre-clip norm reported
    assert float(jnp.abs(mu2["w"]).max()) <= 0.1 * 0.51  # (1-b1)*clipped

def test_cosine_schedule_shape():
    s0 = float(cosine_schedule(jnp.int32(0), warmup=10, total=100))
    sw = float(cosine_schedule(jnp.int32(10), warmup=10, total=100))
    send = float(cosine_schedule(jnp.int32(100), warmup=10, total=100))
    assert s0 == 0.0 and abs(sw - 1.0) < 1e-6 and send == pytest.approx(0.1, abs=1e-6)


# ---------------------------------------------------------------- checkpoint
def test_checkpoint_roundtrip_retention(tmp_path):
    mgr = CheckpointManager(tmp_path, keep=2)
    tree = {"a": jnp.arange(6).reshape(2, 3), "b": {"c": jnp.float32(3.5)},
            "step": jnp.int32(7)}
    for step in (1, 2, 3):
        mgr.save(step, tree, blocking=True)
    assert mgr.latest_step() == 3
    dirs = sorted(d.name for d in tmp_path.iterdir())
    assert dirs == ["step_2", "step_3"]  # retention
    restored = mgr.restore(tree)
    np.testing.assert_array_equal(np.asarray(restored["a"]), np.asarray(tree["a"]))
    assert float(restored["b"]["c"]) == 3.5


def test_checkpoint_atomicity_ignores_tmp(tmp_path):
    mgr = CheckpointManager(tmp_path, keep=3)
    tree = {"a": jnp.zeros(3)}
    mgr.save(5, tree, blocking=True)
    # simulate a crash mid-write
    (tmp_path / "step_9.tmp").mkdir()
    assert mgr.latest_step() == 5


# ---------------------------------------------------------------- trainer
def _tiny_trainer(tmp_path, steps, autotune=False):
    from repro.configs import get_config, reduced
    from repro.data import DataPipeline, PipelineConfig, SyntheticTokenSource
    from repro.train.trainer import Trainer, TrainerConfig

    cfg = reduced(get_config("codeqwen1.5-7b"))
    src = SyntheticTokenSource(128, 33, cfg.vocab_size, seed=0)
    pipe = DataPipeline(src, PipelineConfig(batch_size=4))
    tcfg = TrainerConfig(num_steps=steps, ckpt_every=4, ckpt_dir=str(tmp_path),
                         autotune=autotune, log_every=1000)
    return Trainer(cfg, pipe, tcfg)


def test_trainer_checkpoint_resume_exact(tmp_path):
    out1 = _tiny_trainer(tmp_path, 6).run()
    assert out1["final_step"] == 6
    # a new trainer resumes from the saved step and continues
    t2 = _tiny_trainer(tmp_path, 10)
    out2 = t2.run()
    assert out2["final_step"] == 10
    assert int(out2["state"]["step"]) == 10
    # compare against an uninterrupted run: same pipeline order -> same batches
    t3 = _tiny_trainer(tmp_path / "fresh", 10)
    out3 = t3.run()
    np.testing.assert_allclose(
        np.asarray(out2["state"]["params"]["final_norm"], np.float32),
        np.asarray(out3["state"]["params"]["final_norm"], np.float32),
        rtol=2e-3, atol=2e-3,
    )


def test_trainer_stop_flag_saves(tmp_path):
    t = _tiny_trainer(tmp_path, 50)
    orig = t._step

    def step_and_stop(state, batch):
        out = orig(state, batch)
        if int(out[0]["step"]) >= 3:
            t._stop = True  # simulates SIGTERM handler
        return out

    t._step = step_and_stop
    out = t.run()
    assert out["final_step"] == 3
    assert t.ckpt.latest_step() == 3  # emergency save happened


# ---------------------------------------------------------------- serving
def test_serve_engine_batched_requests():
    from repro.configs import get_config, reduced
    from repro.models import get_api
    from repro.parallel.spec import init_params
    from repro.serve import Request, ServeEngine

    cfg = reduced(get_config("codeqwen1.5-7b"))
    api = get_api(cfg)
    params = init_params(api.param_specs(cfg), jax.random.PRNGKey(0))
    eng = ServeEngine(cfg, params, max_len=64, slots=2)
    reqs = [
        Request(rid=i, prompt=np.arange(1 + i, 5 + i, dtype=np.int32), max_tokens=4)
        for i in range(3)
    ]
    done = eng.run(reqs)
    assert len(done) == 3
    assert all(len(r.tokens) == 4 for r in done)
    # greedy decoding is deterministic: same prompt -> same continuation
    eng2 = ServeEngine(cfg, params, max_len=64, slots=2)
    again = eng2.run([Request(rid=9, prompt=np.arange(1, 5, dtype=np.int32), max_tokens=4)])
    assert again[0].tokens == [t for t in done[0].tokens]
