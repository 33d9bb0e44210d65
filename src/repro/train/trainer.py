"""Fault-tolerant trainer with the paper's autotuner in the loop.

Per step: data-wait (telemetry) -> jit'd train_step -> compute telemetry.
Every ``autotune_every`` steps the OnlineAutotuner ingests the telemetry
window as a new observation, refits its predictor, and — if a reconfiguration
is predicted to beat the current pipeline by >=10% — live-reconfigures the
pipeline (workers / prefetch / block size). This is the paper's contribution
running *inside* the trainer, and doubles as straggler self-mitigation: a
host whose storage degrades re-tunes from its own local telemetry.

Fault tolerance: atomic async checkpoints every ``ckpt_every`` steps,
auto-resume from the latest on start, SIGTERM/SIGINT -> synchronous
emergency save. The data order is a pure function of (seed, epoch, step),
so restarts are batch-exact. Restore is mesh-shape-agnostic (elastic).
"""

from __future__ import annotations

import dataclasses
import pathlib
import signal
import time
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..core.autotune import ConfigSpace, OnlineAutotuner
from ..checkpoint import CheckpointManager
from ..data.pipeline import DataPipeline
from ..data.telemetry import StepTelemetry
from ..models import ModelConfig, get_api
from ..optim import AdamWConfig
from ..parallel.spec import init_params
from .step import make_train_bundle

__all__ = ["TrainerConfig", "Trainer"]


@dataclasses.dataclass
class TrainerConfig:
    num_steps: int = 100
    ckpt_every: int = 50
    ckpt_dir: str = "/tmp/repro_ckpt"
    ckpt_keep: int = 3
    autotune: bool = True
    autotune_every: int = 10
    log_every: int = 10
    opt: AdamWConfig = dataclasses.field(default_factory=AdamWConfig)
    seed: int = 0


class Trainer:
    def __init__(
        self,
        cfg: ModelConfig,
        pipeline: DataPipeline,
        tcfg: TrainerConfig,
        shape=None,
        make_batch: Optional[Callable] = None,
    ):
        self.cfg = cfg
        self.pipeline = pipeline
        self.tcfg = tcfg
        self.api = get_api(cfg)
        self.telemetry = StepTelemetry(window=max(tcfg.autotune_every, 10))
        self.autotuner = OnlineAutotuner(
            refit_every=tcfg.autotune_every,
            min_observations=8,
            space=ConfigSpace(
                batch_size=(pipeline.config.batch_size,),  # batch fixed by model step
                num_workers=(0, 1, 2, 4),
                block_kb=(16, 64, 256, 1024),
                n_threads=(1,),
                prefetch_depth=(1, 2, 4),
            ),
        )
        self.make_batch = make_batch or self._default_make_batch
        self.ckpt = CheckpointManager(tcfg.ckpt_dir, keep=tcfg.ckpt_keep)
        self._stop = False

        # jit'd step (mesh-free; train.step.make_train_bundle builds a meshed one)
        def step_fn(state, batch):
            def loss_of(p):
                return self.api.loss_fn(cfg, p, batch)

            loss, grads = jax.value_and_grad(loss_of)(state["params"])
            from ..optim import adamw_update, cosine_schedule

            lr_scale = cosine_schedule(state["step"], 10, tcfg.num_steps)
            new_p, mu, nu, om = adamw_update(
                grads, state["params"], state["mu"], state["nu"], state["step"],
                tcfg.opt, lr_scale,
            )
            return (
                {"params": new_p, "mu": mu, "nu": nu, "step": state["step"] + 1},
                {"loss": loss, **om},
            )

        self._step = jax.jit(step_fn, donate_argnums=(0,))

    # ------------------------------------------------------------------
    def _default_make_batch(self, tokens: np.ndarray) -> Dict[str, Any]:
        inp = tokens[:, :-1]
        lab = tokens[:, 1:]
        return {"tokens": jnp.asarray(inp), "labels": jnp.asarray(lab)}

    def init_state(self):
        specs = self.api.param_specs(self.cfg)
        params = init_params(specs, jax.random.PRNGKey(self.tcfg.seed))
        from ..optim import adamw_init_specs

        mu_s, nu_s = adamw_init_specs(specs, self.tcfg.opt)
        mu = init_params(mu_s, jax.random.PRNGKey(0))
        nu = init_params(nu_s, jax.random.PRNGKey(0))
        return {"params": params, "mu": mu, "nu": nu, "step": jnp.int32(0)}

    # ------------------------------------------------------------------
    def run(self) -> Dict[str, Any]:
        state = self.init_state()
        restored = self.ckpt.restore(state)
        start_step = 0
        if restored is not None:
            state = restored
            start_step = int(state["step"])
            print(f"[trainer] resumed from step {start_step}")

        prev_handlers = {}
        for sig in (signal.SIGTERM, signal.SIGINT):
            try:
                prev_handlers[sig] = signal.signal(sig, self._on_signal)
            except ValueError:
                pass  # non-main thread

        history = []
        steps_per_epoch = self.pipeline.steps_per_epoch()
        step = start_step
        try:
            while step < self.tcfg.num_steps and not self._stop:
                epoch = step // steps_per_epoch
                it = self.pipeline.iter_epoch(epoch, start_step=step % steps_per_epoch)
                for tokens in it:
                    if step >= self.tcfg.num_steps or self._stop:
                        it.close()
                        break
                    with self.telemetry.data_wait():
                        batch = self.make_batch(tokens)
                    with self.telemetry.compute():
                        state, metrics = self._step(state, batch)
                        jax.block_until_ready(metrics["loss"])
                    self.telemetry.record_batch(tokens.shape[0], tokens.nbytes)
                    step += 1
                    loss = float(metrics["loss"])
                    history.append(loss)

                    if step % self.tcfg.log_every == 0:
                        print(f"[trainer] step {step} loss {loss:.4f} "
                              f"util {self.telemetry.simulated_utilization():.2%} "
                              f"data_ratio {self.telemetry.data_loading_ratio():.2%}")
                    if self.tcfg.autotune and step % self.tcfg.autotune_every == 0:
                        self._autotune_tick()
                    if step % self.tcfg.ckpt_every == 0:
                        self.ckpt.save(step, state)
        finally:
            self.ckpt.save(step, state, blocking=True)  # emergency/final save
            for sig, h in prev_handlers.items():
                signal.signal(sig, h)
        return {"state": state, "history": history, "final_step": step}

    # ------------------------------------------------------------------
    def _on_signal(self, signum, frame):
        print(f"[trainer] signal {signum}: emergency checkpoint + stop")
        self._stop = True

    def _autotune_tick(self):
        feats = self.telemetry.features(
            batch_size=self.pipeline.config.batch_size,
            num_workers=self.pipeline.config.num_workers,
            block_kb=self.pipeline.config.block_kb,
            prefetch_policy=self.pipeline.config.prefetch_policy,
            lookahead_batches=self.pipeline.config.lookahead_batches,
            cache_budget_mb=self.pipeline.config.cache_budget_mb,
        )
        self.autotuner.observe(feats, feats["throughput_mb_s"])
        self.autotuner.maybe_refit()
        current = {
            "batch_size": self.pipeline.config.batch_size,
            "num_workers": self.pipeline.config.num_workers,
            "block_kb": self.pipeline.config.block_kb,
            "prefetch_depth": self.pipeline.config.prefetch_depth,
            "prefetch_policy": feats["prefetch_policy"],  # numeric code
            "lookahead_batches": self.pipeline.config.lookahead_batches,
            "cache_budget_mb": self.pipeline.config.cache_budget_mb,
        }
        decision = self.autotuner.decide(current, feats)
        if decision.reconfigure:
            knobs = {k: v for k, v in decision.config.items()
                     if k in ("num_workers", "block_kb", "prefetch_depth",
                              "prefetch_policy", "lookahead_batches",
                              "cache_budget_mb")}
            print(f"[autotune] reconfiguring pipeline: {knobs} "
                  f"(predicted +{decision.predicted_gain:.0%})")
            self.pipeline.reconfigure(**knobs)
