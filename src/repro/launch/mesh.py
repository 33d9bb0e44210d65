"""Mesh construction.

Defined as a FUNCTION so importing this module never touches jax device
state. Axes ("data", "model"), with a leading "pod" axis for multi-pod
meshes that carries only data-parallel gradient traffic.

Mesh axes are ``Auto``: the model code places activations with
``with_sharding_constraint`` by logical axis (``parallel/spec.py``) and leaves
the rest to the partitioner, rather than carrying shardings in array types.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType

__all__ = ["make_test_mesh"]


def _auto_mesh(shape, axes):
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_test_mesh(data: int = 2, model: int = 4, pod: int = 0):
    """Small mesh for CI-scale sharding tests (requires
    XLA_FLAGS=--xla_force_host_platform_device_count >= data*model*max(pod,1))."""
    if pod:
        return _auto_mesh((pod, data, model), ("pod", "data", "model"))
    return _auto_mesh((data, model), ("data", "model"))
