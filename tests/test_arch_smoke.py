"""Per-architecture smoke tests (reduced configs): one forward/train step on
CPU asserting output shapes + finite values, plus prefill/decode parity."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS, get_config, reduced
from repro.models import get_api
from repro.parallel.spec import init_params

B, S = 2, 32


def _batch(cfg):
    if cfg.family == "encdec":
        return {
            "frames": jnp.zeros((B, S, cfg.d_model), cfg.dtype),
            "tokens": jnp.zeros((B, cfg.dec_len), jnp.int32),
            "labels": jnp.ones((B, cfg.dec_len), jnp.int32),
        }
    if cfg.family == "vlm":
        return {
            "prefix_embeds": jnp.zeros((B, cfg.prefix_len, cfg.d_model), cfg.dtype),
            "tokens": jnp.zeros((B, S - cfg.prefix_len), jnp.int32),
            "labels": jnp.ones((B, S - cfg.prefix_len), jnp.int32),
        }
    return {"tokens": jnp.zeros((B, S), jnp.int32), "labels": jnp.ones((B, S), jnp.int32)}


@pytest.fixture(scope="module")
def arch_state():
    cache = {}

    def get(name):
        if name not in cache:
            cfg = reduced(get_config(name))
            api = get_api(cfg)
            params = init_params(api.param_specs(cfg), jax.random.PRNGKey(0))
            cache[name] = (cfg, api, params)
        return cache[name]

    return get


@pytest.mark.parametrize("name", sorted(ARCHS))
def test_train_step_smoke(name, arch_state):
    cfg, api, params = arch_state(name)
    loss = jax.jit(lambda p, b: api.loss_fn(cfg, p, b))(params, _batch(cfg))
    assert loss.shape == ()
    assert bool(jnp.isfinite(loss)), name
    # random-init loss should be near ln(vocab)
    assert 0.5 * np.log(cfg.vocab_size) < float(loss) < 2.0 * np.log(cfg.vocab_size)


@pytest.mark.parametrize("name", sorted(ARCHS))
def test_grad_step_smoke(name, arch_state):
    cfg, api, params = arch_state(name)
    g = jax.jit(jax.grad(lambda p, b: api.loss_fn(cfg, p, b)))(params, _batch(cfg))
    leaves = jax.tree.leaves(g)
    assert leaves, name
    assert all(bool(jnp.isfinite(x.astype(jnp.float32)).all()) for x in leaves), name
    total = sum(float(jnp.sum(jnp.abs(x.astype(jnp.float32)))) for x in leaves)
    assert total > 0, name


@pytest.mark.parametrize("name", sorted(ARCHS))
def test_decode_step_smoke(name, arch_state):
    cfg, api, params = arch_state(name)
    cache = init_params(api.init_cache_specs(cfg, B, S), jax.random.PRNGKey(1))
    tok = jnp.zeros((B, 1), jnp.int32)
    logits, new_cache = jax.jit(
        lambda p, c, t: api.decode_step(cfg, p, c, t, jnp.int32(3))
    )(params, cache, tok)
    assert logits.shape == (B, cfg.vocab_padded)
    assert bool(jnp.isfinite(logits).all()), name
    assert jax.tree.structure(new_cache) == jax.tree.structure(cache)


@pytest.mark.parametrize("name", sorted(ARCHS))
def test_prefill_smoke(name, arch_state):
    cfg, api, params = arch_state(name)
    if cfg.family == "encdec":
        arg = jnp.zeros((B, S, cfg.d_model), cfg.dtype)
    else:
        arg = jnp.zeros((B, S), jnp.int32)
    logits = jax.jit(lambda p, t: api.prefill(cfg, p, t))(params, arg)
    assert logits.shape == (B, cfg.vocab_padded)
    assert bool(jnp.isfinite(logits).all()), name


def test_decode_matches_prefill_dense(arch_state):
    """Greedy parity: decoding t tokens step-by-step must equal prefill logits
    at the same position (codeqwen = plain dense causal arch)."""
    cfg, api, params = arch_state("codeqwen1.5-7b")
    T = 8
    rng = np.random.default_rng(0)
    toks = rng.integers(0, cfg.vocab_size, size=(1, T)).astype(np.int32)
    # prefill path: logits of last position
    pl = api.prefill(cfg, params, jnp.asarray(toks))
    # decode path: feed tokens one by one
    cache = init_params(api.init_cache_specs(cfg, 1, T), jax.random.PRNGKey(0))
    for i in range(T):
        dl, cache = api.decode_step(
            cfg, params, cache, jnp.asarray(toks[:, i: i + 1]), jnp.int32(i)
        )
    np.testing.assert_allclose(
        np.asarray(pl, np.float32), np.asarray(dl, np.float32), rtol=2e-2, atol=2e-2
    )


def test_moe_capacity_drops_are_bounded(arch_state):
    """With cf=1.25 on random routing, most tokens keep all top-k slots."""
    cfg, api, params = arch_state("granite-moe-1b-a400m")
    from repro.models.common import moe_dispatch

    rng = jax.random.PRNGKey(0)
    x = jax.random.normal(rng, (256, cfg.d_model), jnp.float32)
    router = jax.random.normal(jax.random.PRNGKey(1), (cfg.d_model, cfg.n_experts), jnp.float32)
    xe, (slot, st, sg, keep), C = moe_dispatch(
        x, router, n_experts=cfg.n_experts_padded, top_k=cfg.top_k,
        capacity_factor=cfg.capacity_factor,
    )
    assert float(keep.mean()) > 0.8
    assert xe.shape == (cfg.n_experts_padded, C, cfg.d_model)


def test_remat_keeps_loss_and_gradients():
    """``cfg.remat`` wraps each layer in ``jax.checkpoint``, which recomputes
    the forward in the backward: the loss and every gradient stay those of
    the step without it (float32, so the two agree to its rounding)."""
    cfg = reduced(get_config("granite-moe-1b-a400m")).replace(dtype=jnp.float32)
    api = get_api(cfg)
    params = init_params(api.param_specs(cfg), jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(1), (B, S + 1), 0, cfg.vocab_size)
    batch = {"tokens": toks[:, :-1], "labels": toks[:, 1:]}

    def loss_and_grads(c):
        return jax.jit(jax.value_and_grad(lambda p: api.loss_fn(c, p, batch)))(params)

    l0, g0 = loss_and_grads(cfg.replace(remat=False))
    l1, g1 = loss_and_grads(cfg.replace(remat=True))
    assert float(l1) == pytest.approx(float(l0), rel=1e-6)
    for a, b in zip(jax.tree.leaves(g0), jax.tree.leaves(g1)):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a), rtol=1e-5, atol=1e-7)


def test_long_context_support_flags():
    from repro.configs import shape_supported

    ok, _ = shape_supported(get_config("falcon-mamba-7b"), "long_500k")
    assert ok
    ok, why = shape_supported(get_config("granite-20b"), "long_500k")
    assert not ok and "full-attention" in why
