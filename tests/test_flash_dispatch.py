"""Where ``attention_heads_tp`` runs the Pallas flash kernel: the predicate
``flash_applies`` takes the training cells' calls and refuses every call the
kernel does not serve; a call it takes runs the kernel only where it is
lowered for a TPU (``jax.lax.platform_dependent``), and the scan elsewhere;
the dispatched call gives the scan path's numbers (the kernel in interpret
mode on the CPU), and on the CPU the model path computes what it computed
before the kernel, to the last bit."""

import functools

import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_config, reduced
from repro.kernels.flash_attention import flash_attention
from repro.models import common, get_api
from repro.parallel.spec import Rules, init_params

B, S = 4, 2048  # the training cells' rows


def _shapes(name, seq=S):
    cfg = get_config(name)
    return ((B, seq, cfg.n_heads, cfg.head_dim), (B, seq, cfg.n_kv_heads, cfg.head_dim))


KW = dict(causal=True, prefix=None, q_offset=0, rules=None)


@pytest.mark.parametrize("name", ["granite-moe-1b-a400m", "granite-4.0-h-micro"])
def test_predicate_takes_the_training_cells_on_a_tpu(name):
    assert common.flash_applies(*_shapes(name), **KW)


def test_predicate_refuses_the_cpu():
    """The predicate reads no backend: a call it takes lowers for the CPU to
    the scan, with no Pallas call in it."""
    assert jax.default_backend() == "cpu"
    q_shape, k_shape = _shapes("granite-moe-1b-a400m", seq=256)
    assert common.flash_applies(q_shape, k_shape, **KW)
    q = jnp.zeros(q_shape, jnp.bfloat16)
    k = jnp.zeros(k_shape, jnp.bfloat16)
    text = jax.jit(common.attention_heads_tp).lower(q, k, k).as_text()
    assert "tpu_custom_call" not in text and "while" in text


@pytest.mark.parametrize("case", [
    dict(prefix=256), dict(q_offset=128), dict(causal=False),
    dict(rules=Rules.make(heads="model")), "keys longer than queries", "unaligned rows",
    "head size 96"])
def test_predicate_refuses_what_the_kernel_does_not_serve(case):
    q, k = _shapes("granite-moe-1b-a400m")
    kw = dict(KW)
    if case == "keys longer than queries":
        k = (B, 2 * S) + k[2:]
    elif case == "unaligned rows":
        q, k = _shapes("granite-moe-1b-a400m", seq=2000)
    elif case == "head size 96":
        q, k = q[:3] + (96,), k[:3] + (96,)
    else:
        kw.update(case)
    assert not common.flash_applies(q, k, **kw)


@pytest.mark.parametrize("window", [None, 96])
def test_dispatched_call_gives_the_scan_paths_numbers(monkeypatch, window):
    """On a TPU the call goes to the kernel (here in interpret mode) with the
    call's scale and window; its output and gradients equal the scan's."""
    shape_q, shape_k = (1, 256, 4, 64), (1, 256, 2, 64)
    keys = jax.random.split(jax.random.PRNGKey(3), 4)
    q = jax.random.normal(keys[0], shape_q)
    k = jax.random.normal(keys[1], shape_k)
    v = jax.random.normal(keys[2], shape_k)
    w = jax.random.normal(keys[3], shape_q)

    def loss(q, k, v):
        o = common.attention_heads_tp(q, k, v, window=window, q_chunk=64, scale=0.1)
        return jnp.sum(o * w)

    scan = jax.value_and_grad(loss, (0, 1, 2))(q, k, v)
    calls = []

    def kernel(*args, **kw):
        calls.append(kw)
        return functools.partial(flash_attention, interpret=True, q_block=64,
                                 kv_block=64)(*args, **kw)

    # the TPU's branch, as a lowering for a TPU takes it
    monkeypatch.setattr(common.jax.lax, "platform_dependent",
                        lambda *args, tpu, default: tpu(*args))
    monkeypatch.setattr(common, "flash_attention", kernel)
    flash = jax.value_and_grad(loss, (0, 1, 2))(q, k, v)
    assert calls and all(c["window"] == window and c["scale"] == 0.1 for c in calls)
    for a, b in zip(jax.tree.leaves(flash), jax.tree.leaves(scan)):
        assert float(jnp.max(jnp.abs(a - b))) < 2e-4


# (loss, squared norm of the gradient) of the parent commit, before the
# kernel existed, on the CPU: float.hex of each
BEFORE = {
    "granite-moe-1b-a400m": ("0x1.93c1dc0000000p+2", "0x1.15ceef241da00p+10"),
    "granite-4.0-h-micro": ("0x1.8fc19a0000000p+2", "0x1.fd54dbe61c570p-3"),
}
# the same at a head size of 64, whose calls the predicate takes
BEFORE_64 = {
    "granite-moe-1b-a400m": ("0x1.94b54c0000000p+2", "0x1.7fe761701f300p+11"),
    "granite-4.0-h-micro": ("0x1.8fc4b40000000p+2", "0x1.9db5aa5cc7deap-2"),
}


def _loss_and_norm(cfg):
    api = get_api(cfg)
    params = init_params(api.param_specs(cfg), jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 129), 0, cfg.vocab_size)
    batch = {"tokens": tokens[:, :-1], "labels": tokens[:, 1:]}
    loss, grads = jax.jit(jax.value_and_grad(
        lambda p, b: api.loss_fn(cfg, p, b)))(params, batch)
    norm = sum(float(jnp.sum(jnp.square(x.astype(jnp.float32))))
               for x in jax.tree.leaves(grads))
    return float(loss).hex(), float(norm).hex()


@pytest.mark.parametrize("name", sorted(BEFORE))
def test_model_path_on_the_cpu_is_unchanged_to_the_bit(name):
    assert _loss_and_norm(reduced(get_config(name))) == BEFORE[name]


@pytest.mark.parametrize("name", sorted(BEFORE_64))
def test_dispatched_model_path_on_the_cpu_is_unchanged_to_the_bit(name):
    """Rows of 128 and a head size of 64: the predicate takes the calls, and
    the CPU runs the scan's branch of ``platform_dependent``."""
    cfg = reduced(get_config(name)).replace(head_dim=64)
    assert common.flash_applies((2, 128, cfg.n_heads, 64), (2, 128, cfg.n_kv_heads, 64), **KW)
    assert _loss_and_norm(cfg) == BEFORE_64[name]
