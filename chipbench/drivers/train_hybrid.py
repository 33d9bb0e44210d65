"""Driver of the hybrid training cells: ``Trainer.run`` of a granite-4.0-h
configuration (Mamba-2 mixers beside attention) over a ``DataPipeline``.

It runs the trainer, the pipeline, the window and the spans exactly as
``drivers/train.py`` does, with that driver's ``Feed``, ``_Capture`` and
spanned pipeline; what differs is the model: the configuration is checked
against the program's hybrid, the initial state is
``reference_granite_h.init``'s, and the run's losses, first gradient and
weight change are compared with ``reference_granite_h.follow``, and the
program's chunked SSD (``models/mamba2.py`` ``ssd``) with the reference's
recurrence on seeded inputs at the cell's sizes (``ssd_gap``).  The
record also carries the step's operations (``work/train_hybrid.py``) and
the recurrence's operations and bytes, which ``op_scopes`` puts over the
device time of the program's ``repro.mamba2.ssd`` scope.
"""

from __future__ import annotations

import functools
import gc
import math
from typing import List

import numpy as np

from chipbench import reference_granite_h as ref
from chipbench import reference_train, schedule
from chipbench.drivers.train import (
    WORK_DIR,
    Feed,
    _Capture,
    _pipeline_class,
    change_norms,
    device_peak_bytes,
    flat,
    window_steps,
)
from chipbench.work.train_hybrid import ssd_work, step_flops

# configuration-file key -> ModelConfig field, each of which the program's
# registered configuration has to hold as the file states it
FIELDS = {"hidden_size": "d_model", "num_attention_heads": "n_heads",
          "num_key_value_heads": "n_kv_heads", "shared_intermediate_size": "d_ff",
          "vocab_size": "vocab_size", "num_local_experts": "n_experts",
          "mamba_n_heads": "ssm_heads", "mamba_d_head": "ssm_head_dim",
          "mamba_n_groups": "ssm_groups", "mamba_d_state": "d_state",
          "mamba_d_conv": "d_conv", "mamba_expand": "expand",
          "mamba_chunk_size": "ssm_chunk", "rms_norm_eps": "norm_eps",
          "embedding_multiplier": "embed_multiplier",
          "residual_multiplier": "residual_multiplier",
          "logits_scaling": "logits_divisor", "attention_multiplier": "attn_logit_scale"}


def model_config(config: dict):
    """The program's configuration of ``config``: its registered model cut
    to the file's depth."""
    from repro.configs import get_config

    return get_config(config["program_config"]).replace(n_layers=config["num_hidden_layers"])


def check_config(cfg, config: dict, opt) -> List[str]:
    """Where the program would run otherwise than the configuration file
    states.  Each departure is an error."""
    import jax.numpy as jnp

    want = {field: config[key] for key, field in FIELDS.items()}
    want.update({"family": "hybrid", "ssm_version": 2, "act": config["hidden_act"],
                 "gated_mlp": True, "use_rope": config["position_embedding_type"] != "nope",
                 "tie_embeddings": config["tie_word_embeddings"], "embed_scale": False,
                 "rms_plus_one": False,
                 "head_dim": config["hidden_size"] // config["num_attention_heads"],
                 "dtype": jnp.dtype(config["precision"]["weights"]),
                 "vocab_padded": config["vocab_size"], "attn_period": config["as_run"]["period"]})
    errors = [f"program {k}={getattr(cfg, k)!r} differs from the configuration's {v!r}"
              for k, v in want.items() if getattr(cfg, k) != v]
    if config["intermediate_size"] != config["shared_intermediate_size"]:
        # the shared MLP is the only MLP when there are no experts
        errors.append("the configuration's intermediate_size and shared_intermediate_size differ")
    types = ["attention" if cfg.is_attn_layer(i) else "mamba" for i in range(cfg.n_layers)]
    if types != config["layer_types"]:
        errors.append(f"program layer types {types} differ from the configuration's")
    if (config["mamba_conv_bias"], config["mamba_proj_bias"], config["attention_bias"]) != \
            (True, False, False):
        errors.append("the program has a conv bias and no projection or attention bias")
    o = config["optimizer"]
    for k in ("lr", "b1", "b2", "eps", "weight_decay", "clip_norm"):
        if getattr(opt, k) != o[k]:
            errors.append(f"program optimizer {k}={getattr(opt, k)} differs from {o[k]}")
    if jnp.dtype(opt.moment_dtype) != jnp.dtype(config["precision"]["adam_moments"]):
        errors.append(f"program moments are {opt.moment_dtype}")
    return errors


class _Start:
    """The trainer's checkpoint manager for a cell: it keeps nothing, and
    its restore hands the trainer the cell's initial state (the weights
    ``reference_granite_h.init`` makes from the seed, zero moments, step 0)
    in place of the one the trainer made, whose buffers it frees first."""

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
        made = ref.init(self.model, self.seed)
        paths = set(flat(template["params"]))
        if paths != set(made):
            self.errors.append(f"program leaves {sorted(paths ^ set(made))} are not "
                               f"both the program's and the configuration's")

        def leaf(path, x):
            key = "/".join(p.key for p in path)
            y = made.get(key)
            if y is None or (y.shape, y.dtype) != (x.shape, x.dtype):
                self.errors.append(f"program leaf {key} is {x.dtype}{list(x.shape)}, "
                                   f"the configuration's {getattr(y, 'dtype', None)}"
                                   f"{list(getattr(y, 'shape', []))}")
                return jnp.zeros(x.shape, x.dtype)
            return y

        params = jax.tree_util.tree_map_with_path(leaf, template["params"])
        zeros = [jax.tree.map(lambda x: jnp.zeros(x.shape, x.dtype), template[k])
                 for k in ("mu", "nu")]
        return {"params": params, "mu": zeros[0], "nu": zeros[1], "step": jnp.int32(0)}


class _SSDCapture(_Capture):
    """``_Capture``, which also copies the first gradient of the
    reference's ``SSD_LEAVES`` to the host, from the first moment after
    step 1."""

    ssd = None

    def __call__(self, state, batch):
        if self.calls == 1:
            self.ssd = ref.ssd_grads(flat(state["mu"]), self.b1)
        return super().__call__(state, batch)


def train(cell, run, feed: Feed):
    """Set up and run the cell's trainer; returns what the checks need."""
    import jax

    from repro.data import PipelineConfig, TokenRecordCodec, open_dataset, write_dataset
    from repro.data.storage import StorageBackend
    from repro.train.trainer import Trainer, TrainerConfig

    config, traffic = cell.config, cell.traffic
    cfg = model_config(config)  # a program without this configuration stops here
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
        trainer.ckpt = start = _Start(ref.Model.of(config), run.seed)
        capture = _SSDCapture(trainer, tcfg.opt.b1)
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
    B, S = traffic["batch"], traffic["seq"]
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
        model = ref.Model.of(config)
        opt = reference_train.Optimizer.of(config, traffic["num_steps"])
        program = {"losses": history[:n_ref], "grad_norms": capture.g1,
                   "change_norms": change_norms(capture.p3, capture.p0),
                   "ssd_grads": capture.ssd}
        del capture.p0, capture.p3
        rows = feed.rows[:n_ref]
        sound = ref.follow(model, opt, run.seed, rows)
        sound["ssd_outputs"] = ref.ssd_outputs(model, ref.recurrence(model), run.seed, S)
        program["ssd_outputs"] = ref.ssd_outputs(model, _program_ssd(model.chunk), run.seed, S)
        readings = ref.readings(program, sound)
        control = (_control_readings(model, opt, run.seed, rows, sound, S)
                   if run.control else None)
    limits = traffic["limits"]
    record = {
        "setup_s": feed.starts[warm] - run.t0 if len(feed.starts) > warm else math.inf,
        "window_s": window_s,
        "attempted": n,
        "failed": sum(not math.isfinite(x) for x in steps),
        "memory_peak_bytes": t["memory_peak"],
        "errors": errors,
        "train_tokens": n * B * S,
        "step_s": [float(x) for x in np.diff(feed.starts[warm:warm + n + 1])],
        "device_kind": jax.devices()[0].device_kind,
        "flops_per_step": step_flops(config, B, S),
        "ssd_work_per_step": ssd_work(config, B, S),
        "checks": {k: {"value": float(readings[k]), "limit": float(limits[k])}
                   for k in limits},
    }
    if feed.trace is not None:
        record["trace"] = feed.trace
    if control is not None:
        record["control_checks"] = control
    return record


def _program_ssd(chunk: int):
    """The program's chunked SSD, looked up when called."""
    from repro.models import mamba2

    return functools.partial(mamba2.ssd, chunk=chunk)


def _control_readings(model, opt, seed: int, rows, sound: dict, seq: int) -> dict:
    """Readings of the float8 control and of the faults a hybrid training
    cell can have, each put in the program's place against the same sound
    reference.  A fault of the recurrence is also read through the
    recurrence alone (``ssd_gap``); the others leave it sound."""
    half = [r[: r.shape[0] // 2] for r in rows]
    kept = {"ssd_outputs": sound["ssd_outputs"]}
    out = {"fp8": ref.readings({**ref.follow(model, opt, seed, rows, quant="fp8"), **kept},
                               sound),
           "half_batch": ref.readings({**ref.follow(model, opt, seed, half), **kept}, sound)}
    for fault in ref.FAULTS:
        run = ref.follow(model, opt, seed, rows, fault=fault)
        run["ssd_outputs"] = ref.ssd_outputs(model, ref.recurrence(model, fault), seed, seq)
        out[fault] = ref.readings(run, sound)
    zeros = {k: 0.0 for k in sound["grad_norms"]}
    out["state_unchanged"] = ref.readings(
        {"losses": sound["losses"], "grad_norms": zeros, "change_norms": zeros,
         "ssd_grads": {k: np.zeros_like(v) for k, v in sound["ssd_grads"].items()}, **kept},
        sound)
    return out
