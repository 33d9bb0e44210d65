"""Driver of the training cells: ``Trainer.run`` over a ``DataPipeline``.

Set-up writes the cell's token records from the seed (packed, on an
unthrottled storage backend named after the tmpfs tier, in a directory of
the checkout), builds the pipeline and the ``Trainer`` of the
configuration, and starts ``Trainer.run``: the first ``warmup_steps`` steps
compile the step and are set-up too.  The window runs from the start of the
next step to the end of the first step that ends ``--seconds`` after it.
Steps are timed through the trainer's ``make_batch`` hook, which opens each
step; the driver ends the run through the trainer's own stop flag, which
its signal handler sets.  With ``--trace 1`` the profiler records steps
``after_steps + 1`` to ``after_steps + steps``.

Host spans: ``chipbench.train.fetch`` (the wait on the pipeline's
iterator), ``chipbench.train.make_batch`` (the host-to-device copy) and
``chipbench.train.step`` (one step interval, from one ``make_batch`` to
the next).

Two things are set around the program, never inside a step:

- The trainer's checkpoint manager is replaced by one that keeps nothing
  and that restores the cell's initial state: the published initialiser's
  weights, made from the seed on the device (``reference_train.init``).
  ``Trainer.run`` resumes from what it restores, as it would from a
  checkpoint, in place of its own initialisation (one N(0, 1) / sqrt(its
  leading axis) for each leaf, under which every attention softmax is
  one-hot and the step's gradient moves by a fifth under any change of
  rounding).  It saves the whole state (11 GB as float32 ``.npz``) when it
  ends, and a check runs the cell many times on one disk, so the manager
  writes nothing.  No save falls in the window: the cell's ``ckpt_every``
  is past its last step.
- Until the fourth step starts, the step is called through a wrapper that
  copies the weights to the host before the first step and after the
  third, and reads the first moment's leaf norms after the first.

Once the window has closed and the state is freed, the reference
(``chipbench/reference_train.py``) follows the first three steps from the
seed's weights on the rows the pipeline fed them, and the run's losses,
first gradient and weight change are compared with it.
"""

from __future__ import annotations

import gc
import math
import pathlib
import time
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from chipbench import reference_train, schedule
from chipbench.work.train import step_flops

WORK_DIR = pathlib.Path(__file__).resolve().parents[2] / ".chipbench"
SPAN_FETCH = "chipbench.train.fetch"
SPAN_BATCH = "chipbench.train.make_batch"
SPAN_STEP = "chipbench.train.step"

# configuration-file key -> ModelConfig field
FIELDS = {"num_hidden_layers": "n_layers", "hidden_size": "d_model",
         "num_attention_heads": "n_heads", "num_key_value_heads": "n_kv_heads",
         "head_dim": "head_dim", "intermediate_size": "d_ff", "vocab_size": "vocab_size",
         "num_local_experts": "n_experts", "num_experts_per_tok": "top_k",
         "rope_theta": "rope_theta"}


def model_config(config: dict):
    """The program's configuration of ``config``: its registered model with
    the file's sizes, rope theta and capacity factor."""
    from repro.configs import get_config

    return get_config(config["program_config"]).replace(
        capacity_factor=config["as_run"]["capacity_factor"],
        **{field: config[key] for key, field in FIELDS.items()})


def check_config(cfg, config: dict, opt) -> List[str]:
    """Where the program would run otherwise than the configuration file
    states.  Each departure is an error."""
    import jax.numpy as jnp

    run = config["as_run"]
    want = {"family": "moe", "act": config["hidden_act"], "gated_mlp": True,
            "tie_embeddings": config["tie_word_embeddings"], "embed_scale": False,
            "rms_plus_one": False, "moe_period": 1, "router_renormalize": True,
            "window": None, "local_global_period": 0,
            "dtype": jnp.dtype(config["precision"]["weights"]),
            "vocab_padded": run["vocab_rows"], "n_experts_padded": run["expert_rows"]}
    errors = [f"program {k}={getattr(cfg, k)!r} differs from the configuration's {v!r}"
              for k, v in want.items() if getattr(cfg, k) != v]
    scale = cfg.attn_logit_scale or cfg.head_dim ** -0.5
    if scale != run["attention_scale"]:
        errors.append(f"program attention scale {scale} differs from {run['attention_scale']}")
    o = config["optimizer"]
    for k in ("lr", "b1", "b2", "eps", "weight_decay", "clip_norm"):
        if getattr(opt, k) != o[k]:
            errors.append(f"program optimizer {k}={getattr(opt, k)} differs from {o[k]}")
    if jnp.dtype(opt.moment_dtype) != jnp.dtype(config["precision"]["adam_moments"]):
        errors.append(f"program moments are {opt.moment_dtype}")
    return errors


def window_steps(starts: Sequence[float], warmup: int, seconds: float,
                 min_steps: int = 1) -> Optional[Tuple[int, float]]:
    """Steps and seconds of the window, from ``starts[i]``, the time step
    i + 1 started: it opens as step ``warmup + 1`` starts and closes as the
    first of its steps ends (the next one starts) ``seconds`` or more after
    it opened, holding at least ``min_steps`` steps.  ``None`` while open."""
    if len(starts) <= warmup:
        return None
    t0 = starts[warmup]
    for i in range(warmup + min_steps, len(starts)):
        if starts[i] - t0 >= seconds:
            return i - warmup, starts[i] - t0
    return None


def device_peak_bytes() -> int:
    """Peak device memory of the fullest chip, the step's temporaries
    included: a TPU holds them in ``bytes_reserved``, which
    ``peak_bytes_in_use`` leaves out."""
    import jax

    return max(sum(int((d.memory_stats() or {}).get(k, 0))
                   for k in ("peak_bytes_in_use", "peak_bytes_reserved"))
               for d in jax.local_devices())


def flat(tree) -> Dict[str, object]:
    """A nested dict's leaves by ``a/b/c`` path."""
    import jax

    return {"/".join(p.key for p in path): leaf
            for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]}


def change_norms(after: Dict[str, np.ndarray], before: Dict[str, np.ndarray]) -> Dict[str, float]:
    """The L2 norm of each leaf's change, from host copies."""
    out = {}
    for k, a in after.items():
        b = before[k]
        if a.dtype.itemsize == 2:  # bfloat16: most elements do not move
            moved = a.view(np.uint16) != b.view(np.uint16)
            a, b = a[moved], b[moved]
        d = a.astype(np.float64) - b.astype(np.float64)
        out[k] = float(np.sqrt(np.sum(d * d)))
    return out


class _Start:
    """The trainer's checkpoint manager for a cell: it keeps nothing, and
    its restore hands the trainer the cell's initial state (the weights
    ``reference_train.init`` makes from the seed, zero moments, step 0) in
    place of the one the trainer made, whose buffers it frees first."""

    def __init__(self, model, seed: int):
        self.model, self.seed = model, seed
        self.errors: List[str] = []

    def save(self, step, tree, blocking=False):
        pass

    def wait(self):
        pass

    def restore(self, template):
        import jax
        import jax.numpy as jnp

        for x in jax.tree.leaves(template):
            x.delete()
        made = reference_train.init(self.model, self.seed)

        def leaf(path, x):
            y = made["/".join(p.key for p in path)]
            if (y.shape, y.dtype) != (x.shape, x.dtype):
                self.errors.append(f"program leaf {path} is {x.dtype}{list(x.shape)}, "
                                   f"the configuration's {y.dtype}{list(y.shape)}")
            return y

        params = jax.tree_util.tree_map_with_path(leaf, template["params"])
        zeros = [jax.tree.map(lambda x: jnp.zeros(x.shape, x.dtype), template[k])
                 for k in ("mu", "nu")]
        return {"params": params, "mu": zeros[0], "nu": zeros[1], "step": jnp.int32(0)}


class _Capture:
    """The trainer's step, called through until the fourth step starts:
    it copies the weights to the host before step 1 and after step 3, and
    reads the first moment's leaf norms after step 1."""

    def __init__(self, trainer, b1: float):
        self.trainer, self.step, self.b1 = trainer, trainer._step, b1
        self.calls = 0
        self.p0 = self.p3 = self.g1 = None

    def __call__(self, state, batch):
        import jax

        self.calls += 1
        if self.calls == 1:
            self.p0 = jax.device_get(flat(state["params"]))
        elif self.calls == 2:
            self.g1 = {k: v / (1 - self.b1)
                       for k, v in reference_train.leaf_norms(flat(state["mu"])).items()}
        elif self.calls == 4:
            self.p3 = jax.device_get(flat(state["params"]))
            self.trainer._step = self.step
        return self.step(state, batch)


class Feed:
    """The trainer's ``make_batch`` hook: it opens each step, keeps the rows
    of the first steps, starts and stops the trace, and stops the run once
    the window has closed."""

    def __init__(self, traffic: dict, run):
        self.traffic, self.run = traffic, run
        self.warmup = traffic["warmup_steps"]
        tr = traffic["trace"]
        self.trace_first = tr["after_steps"] + 1
        self.trace_last = tr["after_steps"] + tr["steps"]
        self.min_steps = self.trace_last - self.warmup if run.trace else 1
        self.starts: List[float] = []
        self.rows: List[np.ndarray] = []
        self.trace = None
        self.trainer = None
        self.inner = None
        self._span = None

    def _close_span(self):
        if self._span is not None:
            self._span.__exit__(None, None, None)
            self._span = None

    def __call__(self, tokens):
        import jax

        now = time.monotonic()
        self.starts.append(now)
        k = len(self.starts)  # the step that starts now
        self._close_span()
        if self.run.trace and k == self.trace_last + 1:
            self.trace = self.run.tracer.stop()
        if k <= self.traffic["reference_steps"]:
            self.rows.append(np.array(tokens))
        if window_steps(self.starts, self.warmup, self.run.seconds, self.min_steps):
            self.trainer._stop = True  # this step runs, after the window
        if self.run.trace and k == self.trace_first:
            self.run.tracer.start()
        self._span = jax.profiler.TraceAnnotation(SPAN_STEP)
        self._span.__enter__()
        with jax.profiler.TraceAnnotation(SPAN_BATCH):
            return self.inner(tokens)


def _pipeline_class():
    # built on first use: the program is importable only once the harness
    # has put src/ on the path, after it loaded this driver
    import jax

    from repro.data import DataPipeline

    class SpannedPipeline(DataPipeline):
        """The pipeline, each wait on its epoch iterator in a span."""

        def iter_epoch(self, epoch, start_step=0):
            it = super().iter_epoch(epoch, start_step)
            try:
                while True:
                    with jax.profiler.TraceAnnotation(SPAN_FETCH):
                        tokens = next(it, None)
                    if tokens is None:
                        return
                    yield tokens
            finally:
                it.close()

    return SpannedPipeline


def train(cell, run, feed: Feed):
    """Set up and run the cell's trainer; returns what the checks need."""
    import jax

    from repro.data import PipelineConfig, TokenRecordCodec, open_dataset, write_dataset
    from repro.data.storage import StorageBackend
    from repro.train.trainer import Trainer, TrainerConfig

    config, traffic = cell.config, cell.traffic
    cfg = model_config(config)
    S, B = traffic["seq"], traffic["batch"]
    tokens = schedule.rng(run.seed, "tokens").integers(
        0, config["vocab_size"], size=(traffic["records"], S + 1), dtype=np.int32)
    codec = TokenRecordCodec(S + 1)
    backend = StorageBackend("tmpfs", WORK_DIR / "train-data")
    backend.cleanup()
    reader = pipe = None
    try:
        manifest = write_dataset(backend, "tokens", [codec.encode(t) for t in tokens], "packed")
        reader = open_dataset(backend, manifest)
        pipe = _pipeline_class().from_reader(
            reader, S + 1, PipelineConfig(batch_size=B, num_workers=traffic["num_workers"]))
        tcfg = TrainerConfig(num_steps=traffic["num_steps"],
                             ckpt_every=traffic["num_steps"] + 1,
                             ckpt_dir=str(WORK_DIR / "train-ckpt"), seed=run.seed)
        errors = check_config(cfg, config, tcfg.opt)
        trainer = Trainer(cfg, pipe, tcfg, make_batch=feed)
        feed.trainer, feed.inner = trainer, trainer._default_make_batch
        trainer.ckpt = start = _Start(reference_train.Model.of(config), run.seed)
        capture = _Capture(trainer, tcfg.opt.b1)
        trainer._step = capture
        out = trainer.run()
        feed._close_span()
        memory_peak = device_peak_bytes()
        history = out["history"]
        errors += start.errors
        # drop every hold on the trainer, its state and its compiled step
        del out, trainer
        feed.trainer = feed.inner = capture.trainer = capture.step = None
    finally:
        if pipe is not None:
            pipe.close()
        if reader is not None:
            reader.close()
        backend.cleanup()
    gc.collect()
    jax.clear_caches()
    return {"errors": errors, "history": history, "memory_peak": memory_peak,
            "capture": capture}


def run(cell, run) -> dict:
    import jax

    traffic, config = cell.traffic, cell.config
    feed = Feed(traffic, run)
    t = train(cell, run, feed)
    errors, history, capture = t["errors"], t["history"], t["capture"]
    warm = traffic["warmup_steps"]
    closed = window_steps(feed.starts, warm, run.seconds, feed.min_steps)
    if closed is None:
        errors.append(f"the run ended after {len(history)} steps, before its window closed")
        closed = (max(0, len(feed.starts) - 1 - warm),
                  (feed.starts[-1] - feed.starts[warm]) if len(feed.starts) > warm else 0.0)
    n, window_s = closed
    steps = history[warm:warm + n]
    n_ref = traffic["reference_steps"]
    if capture.p3 is None or capture.g1 is None or len(history) < n_ref:
        errors.append("the run ended before its first steps could be read")
        readings = {k: math.inf for k in traffic["limits"]}
        control = None
    else:
        model = reference_train.Model.of(config)
        opt = reference_train.Optimizer.of(config, traffic["num_steps"])
        program = {"losses": history[:n_ref], "grad_norms": capture.g1,
                   "change_norms": change_norms(capture.p3, capture.p0)}
        del capture.p0, capture.p3
        rows = feed.rows[:n_ref]
        ref = reference_train.follow(model, opt, run.seed, rows)
        readings = reference_train.readings(program, ref)
        control = _control_readings(model, opt, run.seed, rows, ref) if run.control else None
    limits = traffic["limits"]
    record = {
        "setup_s": feed.starts[warm] - run.t0 if len(feed.starts) > warm else math.inf,
        "window_s": window_s,
        "attempted": n,
        "failed": sum(not math.isfinite(x) for x in steps),
        "memory_peak_bytes": t["memory_peak"],
        "errors": errors,
        "train_tokens": n * traffic["batch"] * traffic["seq"],
        "step_s": [float(x) for x in np.diff(feed.starts[warm:warm + n + 1])],
        "device_kind": jax.devices()[0].device_kind,
        "flops_per_step": step_flops(config, traffic["batch"], traffic["seq"]),
        "checks": {k: {"value": float(readings[k]), "limit": float(limits[k])}
                   for k in limits},
    }
    if feed.trace is not None:
        record["trace"] = feed.trace
    if control is not None:
        record["control_checks"] = control
    return record


def _control_readings(model, opt, seed: int, rows, ref: dict) -> dict:
    """Readings of the float8 control and of the faults a training cell can
    have, each put in the program's place against the same reference."""
    import dataclasses

    half = [r[: r.shape[0] // 2] for r in rows]
    zeros = {k: 0.0 for k in ref["grad_norms"]}
    router = "blocks/moe/router"
    return {
        "fp8": reference_train.readings(
            reference_train.follow(model, opt, seed, rows, quant="fp8"), ref),
        "half_batch": reference_train.readings(
            reference_train.follow(model, opt, seed, half), ref),
        "top_k_minus_one": reference_train.readings(
            reference_train.follow(dataclasses.replace(model, top_k=model.top_k - 1),
                                   opt, seed, rows), ref),
        "state_unchanged": reference_train.readings(
            {"losses": ref["losses"], "grad_norms": zeros, "change_norms": zeros}, ref),
        "router_update_doubled": reference_train.readings(
            {**ref, "change_norms": {**ref["change_norms"],
                                     router: 2 * ref["change_norms"][router]}}, ref),
    }
