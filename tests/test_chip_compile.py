"""Ahead-of-time compiles of the main path's device programs for a described
TPU v5e, at real widths: the Pallas mega-grid kernel and the jitted gather
descent that serves ``/predict`` and ``/recommend``.

Nothing runs: the TPU compiler refuses here what the chip would refuse
(tiling, fast-memory use, unsupported ops), so these tests guard the chip
path from any machine that has the TPU compiler installed.  The topology is
described inside a fixture, never while a module is imported, and every
compile of this file stays in this one file and process.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import FEATURE_NAMES
from repro.core.autotune import _MEGA_TAIL_FLOOR, MEGA_GRID_CHUNK
from repro.core.ensemble_base import _predict_packed
from repro.core.features import AUTOTUNE_FEATURE_NAMES
from repro.core.predictor import make_model
from repro.kernels.gbt_predict import gbt_predict
from repro.service.serve import ServeConfig


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            yield topologies.get_topology_desc(platform="tpu",
                                               topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 - any failure means "cannot describe"
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """One described v5e chip, with the persistent compile cache off: an
    entry compiled for a described chip cannot be read back without one."""
    from jax.experimental.compilation_cache import compilation_cache

    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


def _zoo_widths(model: str):
    """(trees, max_depth, node-table width) of a paper model: a full binary
    tree of the configured depth bounds the packed node count."""
    cfg = make_model(model).config
    return cfg.n_estimators, cfg.max_depth, 2 ** (cfg.max_depth + 1) - 1


def _tables(sharding, trees: int, nodes: int):
    def s(dtype):
        return jax.ShapeDtypeStruct((trees, nodes), dtype, sharding=sharding)

    return s(jnp.int32), s(jnp.float32), s(jnp.int32), s(jnp.int32), s(jnp.float32)


@pytest.mark.parametrize("rows", [MEGA_GRID_CHUNK, _MEGA_TAIL_FLOOR])
@pytest.mark.parametrize("model", ["xgboost", "random_forest"])
def test_gbt_kernel_compiles_for_v5e(one_chip, model, rows):
    trees, depth, nodes = _zoo_widths(model)
    scalar = jax.ShapeDtypeStruct((), jnp.float32, sharding=one_chip)
    x = jax.ShapeDtypeStruct((rows, len(FEATURE_NAMES)), jnp.float32,
                             sharding=one_chip)
    compiled = gbt_predict.lower(
        x, *_tables(one_chip, trees, nodes), max_depth=depth,
        base_score=scalar, scale=scalar).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_serving_descent_compiles_for_v5e(one_chip):
    trees, depth, nodes = _zoo_widths("random_forest")
    names = ("feature", "threshold", "left", "right", "value")
    tables = dict(zip(names, _tables(one_chip, trees, nodes)))
    x = jax.ShapeDtypeStruct((ServeConfig().max_batch, len(AUTOTUNE_FEATURE_NAMES)),
                             jnp.float32, sharding=one_chip)
    compiled = _predict_packed.lower(tables, x, max_depth=depth).compile()
    assert compiled.memory_analysis() is not None
