"""The granite-4.0-h hybrid (Mamba-2 mixers beside NoPE attention) against
its plain reference (chipbench/reference_granite_h.py) on seeded random
weights, at a tiny size: a period of 4 with attention at 2, d_model 64, 4
Mamba-2 heads of 16, d_state 8, 512 ids, rows of 32 tokens in SSD chunks
of 8."""

import copy
import functools
import json
import pathlib
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from chipbench import reference_granite_h as ref  # noqa: E402
from repro.configs import get_config  # noqa: E402
from repro.models import get_api, mamba2  # noqa: E402
from repro.parallel.spec import init_params  # noqa: E402

B, S = 2, 32
SIZES = {"hidden_size": 64, "num_attention_heads": 4, "num_key_value_heads": 2,
         "intermediate_size": 128, "shared_intermediate_size": 128, "vocab_size": 512,
         "mamba_n_heads": 4, "mamba_d_head": 16, "mamba_d_state": 8, "mamba_expand": 1,
         "mamba_chunk_size": 8, "num_hidden_layers": 4,
         "layer_types": ["mamba", "mamba", "attention", "mamba"]}
# The program in float32 against the reference: the two differ by the order
# of float32 sums (the chunked SSD regroups the recurrence), under 1e-6
# relative; 1e-4 leaves room and stays 35x under the least fault (the state
# carried in bfloat16 turns A_log's gradient by 3.5e-3).
TOL = 1e-4


def _config() -> dict:
    c = json.loads((ROOT / "chipbench/configs/granite-4.0-h-micro.json").read_text())
    c = copy.deepcopy(c)
    c.update(SIZES)
    c["as_run"]["period"] = 4
    return c


def _program_cfg(dtype=jnp.float32):
    return get_config("granite-4.0-h-micro").replace(
        n_layers=4, d_model=64, n_heads=4, n_kv_heads=2, head_dim=16, d_ff=128,
        vocab_size=512, attn_period=4, attn_phase=2, expand=1, ssm_heads=4,
        ssm_head_dim=16, d_state=8, ssm_chunk=8, q_chunk=16, xent_chunk=64, dtype=dtype)


def _flat(tree):
    return {"/".join(p.key for p in path): leaf
            for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]}


@pytest.fixture(scope="module")
def setup():
    """(program cfg, reference model, reference weights, program weights, batch)."""
    m = ref.Model.of(_config())
    cfg = _program_cfg()
    made = ref.init(m, 2**31 + 7)
    template = get_api(cfg).param_specs(cfg)
    template = init_params(template, jax.random.PRNGKey(0))
    assert set(_flat(template)) == set(made)
    params = jax.tree_util.tree_map_with_path(
        lambda path, x: made["/".join(p.key for p in path)].astype(jnp.float32), template)
    rows = np.random.default_rng(3).integers(0, 512, size=(B, S + 1), dtype=np.int32)
    f32 = {k: v.astype(jnp.float32) for k, v in made.items()}
    return cfg, m, f32, params, rows


def _ref_grad(m, params, rows, fault=""):
    return jax.jit(jax.value_and_grad(
        lambda p: ref.loss(m, p, rows[:, :-1], rows[:, 1:], fault=fault)))(params)


def _gap(g, want):
    """The larger of two gaps between gradients: the largest distance of a
    leaf's, relative to the leaf's norm or the median leaf's, whichever is
    larger; and over the leaves the recurrence alone feeds (A_log and
    dt_bias), relative to the leaf's own norm."""
    norm = {k: float(jnp.linalg.norm(v)) for k, v in want.items()}
    med = float(np.median(list(norm.values())))
    dist = {k: float(jnp.linalg.norm(g[k] - want[k])) for k in want}
    return max(max(dist[k] / max(norm[k], med) for k in want),
               max(dist[k] / norm[k] for k in want if k.endswith(ref.SSD_LEAVES)))


def _inputs(seed, chunks):
    k = jax.random.split(jax.random.PRNGKey(seed), 6)
    T, H, P, G, N = 8 * chunks, 4, 16, 2, 8
    x = jax.random.normal(k[0], (B, T, H, P))
    dt = jax.nn.softplus(jax.random.normal(k[1], (B, T, H)) - 2.0)
    A = -jnp.exp(jax.random.uniform(k[2], (H,), minval=0.0, maxval=2.5))
    Bm = jax.random.normal(k[3], (B, T, G, N))
    Cm = jax.random.normal(k[4], (B, T, G, N))
    w = jax.random.normal(k[5], (B, T, H, P))
    return (x, dt, A, Bm, Cm), w


@pytest.mark.parametrize("chunks", [1, 2, 8])
def test_chunked_ssd_matches_the_sequential_recurrence(chunks):
    """Forward and gradient of the chunked SSD (chunks of 8 steps) against
    the reference's token-by-token recurrence, with 2 groups of 2 heads."""
    args, w = _inputs(chunks, chunks)
    m = ref.Model.of(dict(_config(), mamba_n_groups=2))

    def chunked(*a):
        return jnp.sum(mamba2.ssd(*a, chunk=8) * w)

    def sequential(*a):
        return jnp.sum(ref._recurrence(m, *a, fault="") * w)

    y = mamba2.ssd(*args, chunk=8)
    want = ref._recurrence(m, *args, fault="")
    np.testing.assert_allclose(y, want, rtol=TOL, atol=TOL * float(jnp.abs(want).max()))
    g = jax.grad(chunked, argnums=(0, 1, 2, 3, 4))(*args)
    gw = jax.grad(sequential, argnums=(0, 1, 2, 3, 4))(*args)
    for a, b in zip(g, gw):
        np.testing.assert_allclose(a, b, rtol=TOL, atol=TOL * float(jnp.abs(b).max()))


@pytest.mark.parametrize("impl", ["program", "state_bf16", "no_state_pass"])
def test_ssd_check_holds_the_program_and_catches_each_recurrence_fault(impl):
    """The reference's check of the recurrence alone (``ssd_outputs``, read
    as ``ssd_gap``): the program's chunked SSD keeps within float32's
    regrouping of the sound recurrence; a state carried in bfloat16 or
    reset at every chunk lies past the tolerance."""
    m = ref.Model.of(_config())
    sound = ref.ssd_outputs(m, ref.recurrence(m), 2**31 + 5, S)
    if impl == "program":
        got = ref.ssd_outputs(m, functools.partial(mamba2.ssd, chunk=m.chunk), 2**31 + 5, S)
        assert ref.largest_gap(got, sound) <= TOL
    else:
        got = ref.ssd_outputs(m, ref.recurrence(m, impl), 2**31 + 5, S)
        assert ref.largest_gap(got, sound) > 10 * TOL


def test_program_loss_and_gradient_match_the_reference(setup):
    cfg, m, f32, params, rows = setup
    api = get_api(cfg)
    batch = {"tokens": jnp.asarray(rows[:, :-1]), "labels": jnp.asarray(rows[:, 1:])}
    loss, g = jax.jit(jax.value_and_grad(lambda p: api.loss_fn(cfg, p, batch)))(params)
    want, gw = _ref_grad(m, f32, rows)
    assert abs(float(loss) - float(want)) <= TOL * abs(float(want))
    assert _gap(_flat(g), gw) <= TOL
    # the published multipliers are in play: the loss is near ln V, not 12x off
    assert 0.5 * np.log(512) < float(loss) < 2 * np.log(512)


@pytest.mark.parametrize("fault", ref.FAULTS + ("fp8",))
def test_each_fault_exceeds_the_tolerance(setup, fault):
    """Each fault the chip's cell reads as a control, put in the program's
    place, lies past the tolerance the program keeps."""
    _, m, f32, _, rows = setup
    want, gw = _ref_grad(m, f32, rows)
    if fault == "fp8":
        loss, g = jax.jit(jax.value_and_grad(
            lambda p: ref.loss(m, p, rows[:, :-1], rows[:, 1:], quant="fp8")))(f32)
    else:
        loss, g = _ref_grad(m, f32, rows, fault)
    assert max(abs(float(loss) - float(want)) / abs(float(want)), _gap(g, gw)) > 10 * TOL


def test_decode_through_the_mamba2_cache_matches_prefill(setup):
    cfg, _, _, params, rows = setup
    api = get_api(cfg)
    T = 12
    toks = jnp.asarray(rows[:1, :T])
    want = api.prefill(cfg, params, toks)
    cache = init_params(api.init_cache_specs(cfg, 1, T), jax.random.PRNGKey(0))
    step = jax.jit(lambda c, t, i: api.decode_step(cfg, params, c, t, i))
    for i in range(T):
        logits, cache = step(cache, toks[:, i:i + 1], jnp.int32(i))
    np.testing.assert_allclose(logits, want, rtol=1e-4, atol=1e-4)


def test_bf16_program_trains_near_the_reference(setup):
    """The program at the configuration's bfloat16 weights stays within
    bfloat16 rounding of the float32 reference."""
    _, m, f32, _, rows = setup
    cfg = _program_cfg(jnp.bfloat16)
    api = get_api(cfg)
    made = ref.init(m, 2**31 + 7)
    template = init_params(api.param_specs(cfg), jax.random.PRNGKey(0))
    params = jax.tree_util.tree_map_with_path(
        lambda path, x: made["/".join(p.key for p in path)], template)
    batch = {"tokens": jnp.asarray(rows[:, :-1]), "labels": jnp.asarray(rows[:, 1:])}
    loss = jax.jit(lambda p: api.loss_fn(cfg, p, batch))(params)
    want, _ = _ref_grad(m, f32, rows)
    assert abs(float(loss) - float(want)) <= 1e-2 * abs(float(want))
