"""Production mesh construction.

Defined as a FUNCTION so importing this module never touches jax device
state. Single pod: 16x16 = 256 chips ("data", "model"). Multi-pod: 2 pods x
256 = 512 chips with a leading "pod" axis carrying only data-parallel
gradient traffic (matching slow inter-pod links).

Mesh axes are ``Auto``: the model code places activations with
``with_sharding_constraint`` by logical axis (``parallel/spec.py``) and leaves
the rest to the partitioner, rather than carrying shardings in array types.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType

__all__ = ["make_production_mesh", "make_test_mesh"]


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def _auto_mesh(shape, axes):
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_test_mesh(data: int = 2, model: int = 4, pod: int = 0):
    """Small mesh for CI-scale sharding tests (requires
    XLA_FLAGS=--xla_force_host_platform_device_count >= data*model*max(pod,1))."""
    if pod:
        return _auto_mesh((pod, data, model), ("pod", "data", "model"))
    return _auto_mesh((data, model), ("data", "model"))
