"""Per-kernel allclose tests vs the pure-jnp oracles (each call asks for
interpret mode, so they run on the CPU),
with hypothesis sweeps over shapes and dtypes."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.kernels.flash_attention import block_for, flash_attention
from repro.kernels.ops import gbt_predict_op
from repro.kernels.rmsnorm import rmsnorm
from repro.kernels.ref import (
    attention_reference,
    gbt_predict_reference,
    rmsnorm_reference,
)


def _qkv(key, B, S, H, KV, Dh, dtype):
    k1, k2, k3 = jax.random.split(key, 3)
    q = jax.random.normal(k1, (B, S, H, Dh), dtype)
    k = jax.random.normal(k2, (B, S, KV, Dh), dtype)
    v = jax.random.normal(k3, (B, S, KV, Dh), dtype)
    return q, k, v


TOL = {jnp.float32: 2e-5, jnp.bfloat16: 2e-2}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "mask",
    [dict(causal=True), dict(causal=False), dict(causal=True, window=64),
     dict(causal=True, prefix=32)],
)
def test_flash_attention_masks_dtypes(dtype, mask):
    q, k, v = _qkv(jax.random.PRNGKey(0), 2, 256, 8, 4, 64, dtype)
    o = flash_attention(q, k, v, q_block=64, kv_block=64, interpret=True, **mask)
    r = attention_reference(q, k, v, **mask)
    err = float(jnp.max(jnp.abs(o.astype(jnp.float32) - r.astype(jnp.float32))))
    assert err < TOL[dtype], (mask, err)


@settings(max_examples=10, deadline=None)
@given(
    b=st.integers(1, 3),
    n_blocks=st.integers(1, 4),
    h_pow=st.integers(0, 3),
    g_pow=st.integers(0, 2),
    dh=st.sampled_from([32, 64, 128]),
    causal=st.booleans(),
)
def test_flash_attention_shape_sweep(b, n_blocks, h_pow, g_pow, dh, causal):
    KV = 2 ** h_pow
    H = KV * 2 ** g_pow
    S = 64 * n_blocks
    q, k, v = _qkv(jax.random.PRNGKey(b), b, S, H, KV, dh, jnp.float32)
    o = flash_attention(q, k, v, causal=causal, q_block=64, kv_block=64,
                           interpret=True)
    r = attention_reference(q, k, v, causal=causal)
    assert o.shape == q.shape
    err = float(jnp.max(jnp.abs(o - r)))
    assert err < 2e-5, err


def test_flash_attention_mqa():
    q, k, v = _qkv(jax.random.PRNGKey(9), 2, 128, 8, 1, 64, jnp.float32)
    o = flash_attention(q, k, v, causal=True, q_block=64, kv_block=64,
                           interpret=True)
    r = attention_reference(q, k, v, causal=True)
    assert float(jnp.max(jnp.abs(o - r))) < 2e-5


@pytest.mark.parametrize("n, block", [(2048, 512), (768, 256), (384, 128), (64, 64)])
def test_flash_attention_blocks_follow_the_rows(n, block):
    assert block_for(n) == block


def _grads(attend, q, k, v, w):
    return jax.grad(lambda q, k, v: jnp.sum(attend(q, k, v) * w), (0, 1, 2))(q, k, v)


@pytest.mark.parametrize("head_dim, n_blocks", [(64, 2), (128, 3), (64, 4)])
@pytest.mark.parametrize("groups", [1, 2, 4])
@pytest.mark.parametrize("mask", [dict(causal=True), dict(causal=True, window=96)],
                         ids=["causal", "window"])
def test_flash_attention_gradients(mask, groups, head_dim, n_blocks):
    """Output and the gradients of q, k and v against the dense reference and
    the scan path's ``jax.grad``, with blocks skipped past the diagonal and
    before the window."""
    from repro.models.common import attention_heads_tp

    KV, S = 2, 64 * n_blocks
    q, k, v = _qkv(jax.random.PRNGKey(groups), 1, S, KV * groups, KV, head_dim, jnp.float32)
    w = jax.random.normal(jax.random.PRNGKey(11), q.shape)
    o = flash_attention(q, k, v, q_block=64, kv_block=64, interpret=True, **mask)
    assert float(jnp.max(jnp.abs(o - attention_reference(q, k, v, **mask)))) < 2e-5
    flash = _grads(lambda q, k, v: flash_attention(
        q, k, v, q_block=64, kv_block=64, interpret=True, **mask), q, k, v, w)
    dense = _grads(lambda q, k, v: attention_reference(q, k, v, **mask), q, k, v, w)
    scan = _grads(lambda q, k, v: attention_heads_tp(q, k, v, q_chunk=64, **mask), q, k, v, w)
    for other in (dense, scan):
        for a, b in zip(flash, other):
            assert float(jnp.max(jnp.abs(a - b))) < 1e-4


@pytest.mark.parametrize("q_block, kv_block", [(64, 128), (128, 64)])
def test_flash_attention_gradients_with_unequal_blocks(q_block, kv_block):
    q, k, v = _qkv(jax.random.PRNGKey(5), 2, 256, 4, 2, 64, jnp.float32)
    w = jax.random.normal(jax.random.PRNGKey(12), q.shape)
    mask = dict(causal=True, window=96)
    flash = _grads(lambda q, k, v: flash_attention(
        q, k, v, q_block=q_block, kv_block=kv_block, interpret=True, **mask), q, k, v, w)
    dense = _grads(lambda q, k, v: attention_reference(q, k, v, **mask), q, k, v, w)
    for a, b in zip(flash, dense):
        assert float(jnp.max(jnp.abs(a - b))) < 1e-4


def test_flash_attention_gradients_bf16():
    """bfloat16 operands into the MXU, float32 softmax: gradients within
    bfloat16's rounding of the float32 reference's."""
    q, k, v = _qkv(jax.random.PRNGKey(6), 1, 128, 4, 2, 64, jnp.bfloat16)
    w = jax.random.normal(jax.random.PRNGKey(13), q.shape, jnp.bfloat16)
    flash = _grads(lambda q, k, v: flash_attention(
        q, k, v, q_block=64, kv_block=64, interpret=True), q, k, v, w)
    f32 = [x.astype(jnp.float32) for x in (q, k, v, w)]
    dense = _grads(lambda q, k, v: attention_reference(q, k, v), *f32)
    for a, b in zip(flash, dense):
        a = a.astype(jnp.float32)
        assert float(jnp.max(jnp.abs(a - b))) < 2e-2 * float(jnp.max(jnp.abs(b)))


# ---------------------------------------------------------------- rmsnorm
@settings(max_examples=10, deadline=None)
@given(
    rows=st.integers(1, 300),
    d=st.sampled_from([64, 128, 256, 512]),
    dtype=st.sampled_from([jnp.float32, jnp.bfloat16]),
)
def test_rmsnorm_sweep(rows, d, dtype):
    x = jax.random.normal(jax.random.PRNGKey(rows), (rows, d), dtype)
    s = jax.random.normal(jax.random.PRNGKey(d), (d,), jnp.float32)
    o = rmsnorm(x, s, block_rows=64, interpret=True)
    r = rmsnorm_reference(x, s)
    err = float(jnp.max(jnp.abs(o.astype(jnp.float32) - r.astype(jnp.float32))))
    assert err < (1e-5 if dtype == jnp.float32 else 5e-2)


# ---------------------------------------------------------------- gbt
@settings(max_examples=8, deadline=None)
@given(
    n=st.integers(10, 200),
    n_estimators=st.integers(1, 25),
    depth=st.integers(1, 5),
    seed=st.integers(0, 100),
)
def test_gbt_kernel_sweep(n, n_estimators, depth, seed):
    from repro.core import GBTConfig, GBTRegressor

    rng = np.random.default_rng(seed)
    X = rng.normal(size=(max(n, 12), 7))
    y = np.sin(X[:, 0]) + X[:, 1]
    m = GBTRegressor(GBTConfig(n_estimators=n_estimators, max_depth=depth)).fit(X, y)
    ens = m.ensemble
    pk = np.asarray(gbt_predict_op(X, ens, row_block=64, interpret=True))
    pn = m.predict(X)  # numpy/JAX reference path
    np.testing.assert_allclose(pk, pn, rtol=1e-4, atol=1e-4)


def test_gbt_kernel_vs_jnp_oracle(synth_regression):
    from repro.core import GBTConfig, GBTRegressor

    X, y = synth_regression
    m = GBTRegressor(GBTConfig(n_estimators=12, max_depth=4)).fit(X, y)
    ens = m.ensemble
    pk = gbt_predict_op(X, ens, row_block=128, interpret=True)
    pr = gbt_predict_reference(
        jnp.asarray(X, jnp.float32), ens.feature, ens.threshold, ens.left,
        ens.right, ens.value, ens.max_depth, ens.base_score, ens.scale,
    )
    np.testing.assert_allclose(np.asarray(pk), np.asarray(pr), rtol=1e-5, atol=1e-5)


def test_gbt_table_split_is_exact():
    """Node-table entries (thresholds, values, node indices past bf16's 256)
    split into bf16 parts whose f32 sum is the entry bit for bit, and each
    part is a bf16 value before its cast (no rounding left to elide)."""
    from repro.kernels.gbt_predict import _bf16_head, _split3

    rng = np.random.default_rng(0)
    v = np.concatenate([
        rng.normal(size=4096) * 10.0 ** rng.integers(-6, 6, 4096),
        np.arange(-4096, 4096), [0.0, -1.0, 3.0e38],
    ]).astype(np.float32)
    hi, mid, lo = (np.asarray(p, np.float32) for p in jax.jit(_split3)(v))
    np.testing.assert_array_equal((hi + mid) + lo, v)
    head = np.asarray(_bf16_head(jnp.asarray(v)))
    np.testing.assert_array_equal(head, head.astype(jnp.bfloat16).astype(np.float32))


# ---------------------------------------------------------------- mamba scan
def _mamba_ref(x, dt, B, C, a_log, d_skip):
    A = -jnp.exp(a_log.astype(jnp.float32))
    a = jnp.exp(dt[..., None].astype(jnp.float32) * A)
    bx = (dt.astype(jnp.float32) * x.astype(jnp.float32))[..., None] * \
        B[:, :, None, :].astype(jnp.float32)

    def combine(l, r):
        al, bl = l
        ar, br = r
        return al * ar, ar * bl + br

    _, Bc = jax.lax.associative_scan(combine, (a, bx), axis=1)
    return (Bc * C[:, :, None, :].astype(jnp.float32)).sum(-1) + \
        d_skip * x.astype(jnp.float32)


@settings(max_examples=6, deadline=None)
@given(
    b=st.integers(1, 2),
    n_chunks=st.integers(1, 4),
    di=st.sampled_from([32, 64]),
    ds=st.sampled_from([4, 8, 16]),
    seed=st.integers(0, 50),
)
def test_mamba_scan_kernel_sweep(b, n_chunks, di, ds, seed):
    from repro.kernels.mamba_scan import mamba_scan

    S = 32 * n_chunks
    key = jax.random.PRNGKey(seed)
    ks = jax.random.split(key, 4)
    x = jax.random.normal(ks[0], (b, S, di), jnp.float32)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, S, di), jnp.float32))
    B = jax.random.normal(ks[2], (b, S, ds), jnp.float32)
    C = jax.random.normal(ks[3], (b, S, ds), jnp.float32)
    a_log = jnp.log(jnp.arange(1, ds + 1, dtype=jnp.float32))[None].repeat(di, 0)
    d_skip = jnp.ones((di,), jnp.float32)
    y_k = mamba_scan(x, dt, B, C, a_log, d_skip, chunk=32, di_block=32, interpret=True)
    y_r = _mamba_ref(x, dt, B, C, a_log, d_skip)
    assert float(jnp.max(jnp.abs(y_k - y_r))) < 1e-4


def test_ssm_chunk_local_path_matches_reference():
    """§Perf T1 lever correctness: chunk-local gates == full-seq reference."""
    import numpy as np

    from repro.configs import get_config, reduced
    from repro.models import get_api
    from repro.parallel.spec import init_params

    cfg0 = reduced(get_config("falcon-mamba-7b")).replace(ssm_scan_chunk=8)
    cfg1 = cfg0.replace(ssm_chunk_local=True)
    api = get_api(cfg0)
    params = init_params(api.param_specs(cfg0), jax.random.PRNGKey(0))
    batch = {"tokens": jnp.arange(2 * 32).reshape(2, 32) % cfg0.vocab_size,
             "labels": jnp.ones((2, 32), jnp.int32)}
    l0 = float(api.loss_fn(cfg0, params, batch))
    l1 = float(api.loss_fn(cfg1, params, batch))
    assert abs(l0 - l1) < 1e-5


def test_moe_local_dispatch_matches_full():
    """§Perf T3 lever correctness: sharded local dispatch sums == full."""
    import numpy as np

    from repro.models.common import moe_combine, moe_dispatch, moe_expert_compute

    T, D, E, K = 64, 16, 8, 2
    x = jax.random.normal(jax.random.PRNGKey(1), (T, D), jnp.float32)
    router = jax.random.normal(jax.random.PRNGKey(2), (D, E), jnp.float32)
    w_in = jax.random.normal(jax.random.PRNGKey(3), (E, D, 32), jnp.float32) * 0.1
    w_gate = jax.random.normal(jax.random.PRNGKey(4), (E, D, 32), jnp.float32) * 0.1
    w_out = jax.random.normal(jax.random.PRNGKey(5), (E, 32, D), jnp.float32) * 0.1
    xe, meta, C = moe_dispatch(x, router, n_experts=E, top_k=K, capacity_factor=1.25)
    full = moe_combine(moe_expert_compute(xe, w_in, w_gate, w_out), meta, T, D, E, C,
                       jnp.float32)
    acc = jnp.zeros((T, D), jnp.float32)
    for rank in range(2):
        lo, nl = rank * 4, 4
        xe_l, meta_l, C2 = moe_dispatch(
            x, router, n_experts=E, top_k=K, capacity_factor=1.25,
            expert_lo=lo, n_local=nl)
        acc = acc + moe_combine(
            moe_expert_compute(xe_l, w_in[lo:lo + nl], w_gate[lo:lo + nl],
                               w_out[lo:lo + nl]),
            meta_l, T, D, nl, C2, jnp.float32)
    assert float(jnp.max(jnp.abs(acc - full))) < 1e-5


def test_attn_probs_bf16_close_to_f32():
    """§Perf T2 lever: bf16 probs stay within bf16 tolerance of f32 path."""
    from repro.models.common import attention_heads_tp

    q, k, v = _qkv(jax.random.PRNGKey(7), 2, 128, 8, 4, 64, jnp.float32)
    o32 = attention_heads_tp(q, k, v, q_chunk=64)
    o16 = attention_heads_tp(q, k, v, q_chunk=64, probs_bf16=True)
    assert float(jnp.max(jnp.abs(o32 - o16))) < 3e-2
