"""Ahead-of-time compiles of the main path's device programs for a described
TPU v5e, at real widths: the Pallas mega-grid kernel, the whole-grid program
that ``recommend()`` runs over 10^6 candidates, and the jitted gather
descent that serves ``/predict`` and ``/recommend``, the flash attention
kernel with its backward at every head size its dispatch takes, and the
training cells' whole step, which reaches that kernel.

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
from repro.core.autotune import KNOB_NAMES, _grid_program
from repro.core.ensemble_base import _predict_packed
from repro.core.features import AUTOTUNE_FEATURE_NAMES
from repro.core.predictor import make_model
from repro.kernels import ops
from repro.configs import get_config
from repro.kernels.flash_attention import flash_attention
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


@pytest.mark.parametrize("rows", [8192, 256])
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


# The 10^6-candidate grid of the benchmark's recommend cell and chip_smoke.py.
_GRID_1E6 = (10, 10, 10, 10, 10, 2, 5, 1)


@pytest.mark.parametrize("top_k", [5, None])
@pytest.mark.parametrize("model", ["xgboost", "random_forest"])
def test_grid_program_compiles_for_v5e(one_chip, model, top_k):
    """recommend()'s (top-k) and score_grid()'s (all scores) whole-grid
    program: one compiled Pallas kernel over every row, in well under 1 GB."""
    trees, depth, nodes = _zoo_widths(model)
    names = ("feature", "threshold", "left", "right", "value")

    def s(shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)

    compiled = _grid_program.lower(
        s((len(FEATURE_NAMES),)), s((sum(_GRID_1E6),)),
        dict(zip(names, _tables(one_chip, trees, nodes))), s(()), s(()),
        radices=_GRID_1E6,
        knob_of=tuple(KNOB_NAMES.index(n) if n in KNOB_NAMES else -1
                      for n in FEATURE_NAMES),
        max_depth=depth, pallas=True, descent=ops.gbt_predict_op,
        top_k=top_k).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    assert used < 2**30


def test_serving_descent_compiles_for_v5e(one_chip):
    trees, depth, nodes = _zoo_widths("random_forest")
    names = ("feature", "threshold", "left", "right", "value")
    tables = dict(zip(names, _tables(one_chip, trees, nodes)))
    x = jax.ShapeDtypeStruct((ServeConfig().max_batch, len(AUTOTUNE_FEATURE_NAMES)),
                             jnp.float32, sharding=one_chip)
    compiled = _predict_packed.lower(tables, x, max_depth=depth).compile()
    assert compiled.memory_analysis() is not None


@pytest.mark.parametrize("heads", [16, 32])
def test_flash_attention_and_its_backward_compile_for_v5e(one_chip, heads):
    """The training cells' attention (4 rows of 2048, 8 kv heads of 64; 16
    query heads in granite-moe, 32 in the hybrid): the forward, dq and dkv
    Pallas kernels at the blocks the rows give (512)."""
    B, S, KV, D = 4, 2048, 8, 64

    def s(h):
        return jax.ShapeDtypeStruct((B, S, h, D), jnp.bfloat16, sharding=one_chip)

    def step(q, k, v, w):
        o, vjp = jax.vjp(lambda q, k, v: flash_attention(q, k, v, causal=True), q, k, v)
        return o, vjp(w)

    compiled = jax.jit(step).lower(s(heads), s(KV), s(KV), s(heads)).compile()
    assert compiled.as_text().count("tpu_custom_call") == 3


@pytest.mark.parametrize("heads, kv, head_dim, window", [
    (8, 4, 128, None), (8, 4, 256, None), (16, 8, 64, 512), (8, 4, 256, 1024)])
def test_flash_attention_compiles_for_v5e_at_other_head_sizes_and_windows(
        one_chip, heads, kv, head_dim, window):
    """The other head sizes that ``models.common.flash_applies`` takes, and
    sliding windows (gemma3's local layers: 1024 rows, heads of 256)."""
    B, S = 2, 2048

    def s(h):
        return jax.ShapeDtypeStruct((B, S, h, head_dim), jnp.bfloat16, sharding=one_chip)

    def step(q, k, v, w):
        o, vjp = jax.vjp(lambda q, k, v: flash_attention(
            q, k, v, causal=True, window=window), q, k, v)
        return o, vjp(w)

    compiled = jax.jit(step).lower(s(heads), s(kv), s(kv), s(heads)).compile()
    assert compiled.as_text().count("tpu_custom_call") == 3


@pytest.mark.parametrize("name, layers", [
    ("granite-moe-1b-a400m", dict(n_layers=1)),
    ("granite-4.0-h-micro", dict(n_layers=2, attn_period=2, attn_phase=1))],
    ids=["granite-moe", "hybrid"])
def test_training_step_compiles_for_v5e_through_the_flash_kernel(one_chip, tmp_path,
                                                                   name, layers):
    """``Trainer._step`` at a training cell's widths and rows (4 x 2048),
    cut to one attention layer, lowered for a v5e: its Pallas calls are the
    kernel's forward (the remat runs it again), dq and dkv, each under the
    scope ``repro.attention.flash``."""
    import types

    from repro.train.trainer import Trainer, TrainerConfig

    cfg = get_config(name).replace(**layers)
    pipe = types.SimpleNamespace(config=types.SimpleNamespace(batch_size=4))
    trainer = Trainer(cfg, pipe, TrainerConfig(ckpt_dir=str(tmp_path)))

    def on_chip(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip)

    state = jax.tree.map(on_chip, jax.eval_shape(trainer.init_state))
    rows = jax.ShapeDtypeStruct((4, 2048), jnp.int32, sharding=one_chip)
    text = trainer._step.lower(state, {"tokens": rows, "labels": rows}).compile().as_text()
    calls = [line for line in text.splitlines() if 'custom_call_target="tpu_custom_call"' in line]
    assert len(calls) == 4 and all("repro.attention.flash" in line for line in calls)
