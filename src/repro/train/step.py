"""train_step / serve_step builders: pure functions + their sharding specs.

The returned functions close over (cfg, api, ctx) and take explicit state so
they lower under pjit with in/out shardings derived from the logical rules.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..models import ModelConfig, ShardCtx, get_api
from ..optim import AdamWConfig, adamw_init_specs, adamw_update, cosine_schedule
from ..parallel.rules import make_rules, mesh_dp_axes
from ..parallel.spec import Rules, abstract_params, partition_spec, tree_partition_specs

__all__ = ["StepBundle", "make_train_bundle", "make_serve_bundle"]


@dataclasses.dataclass
class StepBundle:
    """A lowered-able step: fn + abstract inputs + in/out shardings."""

    fn: Callable
    in_shardings: Any
    out_shardings: Any
    abstract_inputs: Tuple
    donate_argnums: Tuple[int, ...] = ()
    ctx: Optional[ShardCtx] = None


def _batch_pspec(inputs: Dict[str, jax.ShapeDtypeStruct], rules: Rules):
    """Batch arrays shard on their leading batch dim."""
    out = {}
    for k, v in inputs.items():
        if k == "pos":
            out[k] = P()
        else:
            out[k] = partition_spec(("batch",) + (None,) * (len(v.shape) - 1), rules)
    return out


def make_train_bundle(
    cfg: ModelConfig,
    shape,
    mesh=None,
    multi_pod: bool = False,
    opt: Optional[AdamWConfig] = None,
    rules: Optional[Rules] = None,
    total_steps: int = 10_000,
    warmup: int = 200,
    accum_steps: int = 1,
) -> StepBundle:
    """``accum_steps > 1`` enables gradient accumulation: the global batch is
    split into microbatches scanned sequentially (f32 grad accumulator), so
    per-device activation memory scales down ~accum_steps x at identical
    optimizer semantics — the standard lever for fitting long-sequence train
    steps in HBM."""
    from ..configs import input_specs  # local import to avoid cycle

    api = get_api(cfg)
    opt = opt or AdamWConfig()
    rules = rules or make_rules(cfg, "train", shape.global_batch, multi_pod)
    ctx = ShardCtx(mesh=mesh, rules=rules, dp_axes=mesh_dp_axes(multi_pod))

    pspecs = api.param_specs(cfg)
    mu_specs, nu_specs = adamw_init_specs(pspecs, opt)
    state_specs = {"params": pspecs, "mu": mu_specs, "nu": nu_specs}
    state_pspec = {
        **tree_partition_specs(state_specs, rules),
        "step": P(),
    }
    spec = input_specs(cfg, shape)
    batch_abstract = spec["inputs"]
    batch_pspec = _batch_pspec(batch_abstract, rules)

    def _grads_of(params, batch):
        if accum_steps <= 1:
            return jax.value_and_grad(
                lambda p: api.loss_fn(cfg, p, batch, ctx)
            )(params)

        def split(x):  # [B, ...] -> [accum, B/accum, ...]
            assert x.shape[0] % accum_steps == 0, (x.shape, accum_steps)
            return x.reshape(accum_steps, x.shape[0] // accum_steps, *x.shape[1:])

        micro = jax.tree.map(split, batch)
        zeros = jax.tree.map(
            lambda p: jnp.zeros(p.shape, jnp.float32), params
        )

        def body(carry, mb):
            lsum, gsum = carry
            loss, grads = jax.value_and_grad(
                lambda p: api.loss_fn(cfg, p, mb, ctx)
            )(params)
            gsum = jax.tree.map(lambda a, g: a + g.astype(jnp.float32), gsum, grads)
            return (lsum + loss, gsum), None

        (lsum, gsum), _ = jax.lax.scan(body, (jnp.zeros((), jnp.float32), zeros), micro)
        inv = 1.0 / accum_steps
        return lsum * inv, jax.tree.map(lambda g: g * inv, gsum)

    def train_step(state, batch):
        loss, grads = _grads_of(state["params"], batch)
        lr_scale = cosine_schedule(state["step"], warmup, total_steps)
        new_params, new_mu, new_nu, om = adamw_update(
            grads, state["params"], state["mu"], state["nu"], state["step"], opt, lr_scale
        )
        new_state = {
            "params": new_params,
            "mu": new_mu,
            "nu": new_nu,
            "step": state["step"] + 1,
        }
        return new_state, {"loss": loss, **om}

    abstract_state = {
        "params": abstract_params(pspecs),
        "mu": abstract_params(mu_specs),
        "nu": abstract_params(nu_specs),
        "step": jax.ShapeDtypeStruct((), jnp.int32),
    }
    return StepBundle(
        fn=train_step,
        in_shardings=(state_pspec, batch_pspec),
        out_shardings=(state_pspec, {"loss": P(), "grad_norm": P()}),
        abstract_inputs=(abstract_state, batch_abstract),
        donate_argnums=(0,),
        ctx=ctx,
    )


def make_serve_bundle(
    cfg: ModelConfig, shape, mesh=None, multi_pod: bool = False,
    rules: Optional[Rules] = None,
) -> StepBundle:
    from ..configs import input_specs

    api = get_api(cfg)
    rules = rules or make_rules(cfg, "decode", shape.global_batch, multi_pod)
    ctx = ShardCtx(mesh=mesh, rules=rules, dp_axes=mesh_dp_axes(multi_pod))
    spec = input_specs(cfg, shape)
    cache_pspec = tree_partition_specs(spec["cache_specs"], rules)
    pspecs = api.param_specs(cfg)
    param_pspec = tree_partition_specs(pspecs, rules)
    logits_pspec = partition_spec(("batch", "vocab"), rules)

    def serve_step(params, cache, token, pos):
        logits, new_cache = api.decode_step(cfg, params, cache, token, pos, ctx)
        return logits, new_cache

    tok_pspec = partition_spec(("batch", None), rules)
    return StepBundle(
        fn=serve_step,
        in_shardings=(param_pspec, cache_pspec, tok_pspec, P()),
        out_shardings=(logits_pspec, cache_pspec),
        abstract_inputs=(
            abstract_params(pspecs),
            spec["cache"],
            spec["inputs"]["token"],
            spec["inputs"]["pos"],
        ),
        donate_argnums=(1,),
        ctx=ctx,
    )

