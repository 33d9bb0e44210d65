"""Logical-axis sharding system (MaxText-style, hand-rolled).

Every parameter/activation declares *logical* axes ("vocab", "embed", "mlp",
"heads", "expert", "batch", "seq", ...). A ``Rules`` mapping assigns logical
axes to mesh axes; changing the mapping re-shards the whole model with no
model code changes.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

__all__ = ["ParamSpec", "Rules", "DEFAULT_RULES", "POD_RULES", "partition_spec",
           "tree_partition_specs", "abstract_params", "init_params", "logical_constraint"]

MeshAxes = Union[None, str, Tuple[str, ...]]


@dataclasses.dataclass(frozen=True)
class ParamSpec:
    """Declarative parameter: shape + dtype + logical axes + init scale."""

    shape: Tuple[int, ...]
    axes: Tuple[Optional[str], ...]
    dtype: Any = jnp.bfloat16
    init: str = "normal"  # normal | zeros | ones | mamba_a | mamba2_a | mamba2_dt
    scale: float = 1.0

    def __post_init__(self):
        assert len(self.shape) == len(self.axes), (self.shape, self.axes)


@dataclasses.dataclass(frozen=True)
class Rules:
    """logical axis -> mesh axis (or tuple of mesh axes, or None=replicated)."""

    table: Tuple[Tuple[str, MeshAxes], ...]

    @classmethod
    def make(cls, **kw: MeshAxes) -> "Rules":
        return cls(tuple(sorted(kw.items())))

    def get(self, logical: Optional[str]) -> MeshAxes:
        if logical is None:
            return None
        for k, v in self.table:
            if k == logical:
                return v
        return None

    def replace(self, **kw: MeshAxes) -> "Rules":
        d = dict(self.table)
        d.update(kw)
        return Rules(tuple(sorted(d.items())))


# Baseline rules: DP over (pod, data); TP/EP/SP over model.
DEFAULT_RULES = Rules.make(
    batch=("data",),
    expert="model",
    heads="model",
    kv_heads="model",
    mlp="model",
    vocab="model",
    embed=None,
    seq=None,
    kv_seq="model",     # decode KV-cache sequence sharding (MQA/GQA fallback)
    act_seq=None,       # activation sequence dim (sequence parallelism)
    state=None,
    layers=None,
    conv=None,
    capacity=None,
    frames=None,
)

POD_RULES = DEFAULT_RULES.replace(batch=("pod", "data"))


def partition_spec(axes: Sequence[Optional[str]], rules: Rules) -> P:
    mesh_axes = []
    used: set = set()
    for a in axes:
        m = rules.get(a)
        if m is None:
            mesh_axes.append(None)
            continue
        ms = (m,) if isinstance(m, str) else tuple(m)
        ms = tuple(x for x in ms if x not in used)
        used.update(ms)
        if not ms:
            mesh_axes.append(None)
        elif len(ms) == 1:
            mesh_axes.append(ms[0])
        else:
            mesh_axes.append(ms)
    # strip trailing Nones for tidiness
    while mesh_axes and mesh_axes[-1] is None:
        mesh_axes.pop()
    return P(*mesh_axes)


def tree_partition_specs(spec_tree, rules: Rules):
    return jax.tree.map(
        lambda s: partition_spec(s.axes, rules),
        spec_tree,
        is_leaf=lambda x: isinstance(x, ParamSpec),
    )


def abstract_params(spec_tree):
    """ShapeDtypeStruct tree — used by the dry-run (no allocation)."""
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype),
        spec_tree,
        is_leaf=lambda x: isinstance(x, ParamSpec),
    )


def _init_one(key, s: ParamSpec):
    if s.init == "zeros":
        return jnp.zeros(s.shape, s.dtype)
    if s.init == "ones":
        return jnp.ones(s.shape, s.dtype)
    if s.init == "mamba_a":
        # mamba A_log init: log(1..d_state) broadcast
        n = s.shape[-1]
        a = jnp.log(jnp.arange(1, n + 1, dtype=jnp.float32))
        return jnp.broadcast_to(a, s.shape).astype(s.dtype)
    if s.init == "mamba2_a":
        # Mamba-2 A_log: log of A ~ U[1, 16], one per head
        return jnp.log(jax.random.uniform(key, s.shape, jnp.float32, 1.0, 16.0)).astype(s.dtype)
    if s.init == "mamba2_dt":
        # Mamba-2 dt_bias: softplus^-1(dt), dt log-uniform in [1e-3, 1e-1], floored at 1e-4
        return _mamba2_dt_bias(key, s.shape).astype(s.dtype)
    fan_in = s.shape[0] if len(s.shape) >= 2 else max(s.shape[0], 1)
    std = s.scale / np.sqrt(fan_in)
    return (jax.random.normal(key, s.shape, jnp.float32) * std).astype(s.dtype)


def _mamba2_dt_bias(key, shape):
    """The Mamba-2 dt_bias initialiser: the inverse softplus of a dt drawn
    log-uniformly from [1e-3, 1e-1] and floored at 1e-4."""
    lo, hi = np.log(1e-3), np.log(1e-1)
    dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32, lo, hi))
    dt = jnp.maximum(dt, 1e-4)
    return dt + jnp.log(-jnp.expm1(-dt))


def init_params(spec_tree, key):
    leaves, treedef = jax.tree.flatten(
        spec_tree, is_leaf=lambda x: isinstance(x, ParamSpec)
    )
    keys = jax.random.split(key, len(leaves))
    return jax.tree.unflatten(treedef, [_init_one(k, s) for k, s in zip(keys, leaves)])


def logical_constraint(x, axes: Sequence[Optional[str]], rules: Optional[Rules]):
    """with_sharding_constraint by logical axes (no-op outside pjit/mesh)."""
    if rules is None:
        return x
    try:
        return jax.lax.with_sharding_constraint(x, partition_spec(axes, rules))
    except (ValueError, RuntimeError) as e:
        # no mesh in scope (single-device unit tests) or indivisible dim
        if "mesh" in str(e) or "divisible" in str(e) or isinstance(e, ValueError):
            return x
        raise
