"""The training cell's plain reference against the program at a tiny size
on the CPU: the same parameter tree, the same loss and gradient when the
program computes in float32, the same dropped assignments, and controls
that fail the limits at this size."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import reference_train as rt
from chipbench.drivers import train as drv
from chipbench.tests import tiny_train

SEED = 2**31 + 5


@pytest.fixture(scope="module")
def tiny():
    cell = tiny_train.cell()
    return cell, rt.Model.of(cell.config)


def _program(config, dtype):
    from repro.models import get_api

    cfg = drv.model_config(config).replace(dtype=jnp.dtype(dtype))
    return cfg, get_api(cfg)


def _nest(flat_params, like):
    return jax.tree_util.tree_map_with_path(
        lambda path, _: flat_params["/".join(p.key for p in path)], like)


def _rows(cell, seed, n=4):
    t = cell.traffic
    return np.random.default_rng(seed).integers(
        0, cell.config["vocab_size"], (n, t["seq"] + 1), dtype=np.int32)


@pytest.mark.parametrize("sizes", ["tiny", "cell"])
def test_reference_leaves_are_the_program_tree(sizes, tiny):
    from repro.optim import AdamWConfig
    from repro.parallel.spec import abstract_params

    from chipbench import run as harness

    cell = tiny[0] if sizes == "tiny" else harness.resolve(tiny_train.CELL)
    cfg, api = _program(cell.config, "bfloat16")
    assert drv.check_config(cfg, cell.config, AdamWConfig()) == []
    program = drv.flat(abstract_params(api.param_specs(cfg)))
    ref = {p: (s, jnp.dtype(d)) for p, s, d, _ in rt.leaves(rt.Model.of(cell.config))}
    assert {k: (tuple(v.shape), v.dtype) for k, v in program.items()} == ref


def test_initial_weights_follow_the_published_initialiser(tiny):
    _, m = tiny
    p, again, other = rt.init(m, SEED), rt.init(m, SEED), rt.init(m, SEED + 1)
    for path, shape, dtype, kind in rt.leaves(m):
        x = np.asarray(p[path], np.float64)
        assert x.shape == shape and p[path].dtype == jnp.dtype(dtype)
        assert np.array_equal(x, np.asarray(again[path], np.float64))
        if kind == "ones":
            assert np.all(x == 1.0)
        else:
            assert abs(x.std() - 0.02) < 0.002
            assert not np.array_equal(x, np.asarray(other[path], np.float64))


@pytest.mark.parametrize("drops", [False, True], ids=["no_drops", "drops"])
def test_float32_program_loss_and_gradient_match_the_reference(drops, tiny):
    from repro.parallel.spec import abstract_params

    cell, m = tiny
    cfg, api = _program(cell.config, "float32")
    params = rt.init(m, SEED)
    params = {k: v.astype(jnp.float32) for k, v in params.items()}
    if drops:  # every token's router prefers expert 0 far over capacity
        params["blocks/moe/router"] = params["blocks/moe/router"].at[:, :, 0].add(0.5)
    rows = jnp.asarray(_rows(cell, 3))
    tok, lab = rows[:, :-1], rows[:, 1:]
    like = abstract_params(api.param_specs(cfg))
    with jax.default_matmul_precision("highest"):
        lp, gp = jax.value_and_grad(lambda p: api.loss_fn(
            cfg, p, {"tokens": tok, "labels": lab}))(_nest(params, like))
        lr, gr = jax.value_and_grad(lambda p: rt.loss(m, p, tok, lab))(params)
    assert abs(float(lp) - float(lr)) <= 1e-5 * abs(float(lr))
    gp = drv.flat(gp)
    for k in gr:
        a, b = np.asarray(gp[k], np.float64), np.asarray(gr[k], np.float64)
        assert np.linalg.norm(a - b) <= 1e-3 * np.linalg.norm(b), k


def test_capacity_drops_the_same_assignments_as_the_program(tiny):
    from repro.models.common import moe_dispatch

    cell, m = tiny
    T = 4 * cell.traffic["seq"]
    key = jax.random.PRNGKey(1)
    x = jax.random.normal(key, (T, m.d_model), jnp.float32)
    router = 0.02 * jax.random.normal(jax.random.PRNGKey(2), (m.d_model, m.experts))
    router = router.at[:, 0].add(0.5)  # expert 0 overflows
    with jax.default_matmul_precision("highest"):
        _, (_, st, _, keep), C = moe_dispatch(
            x, router, n_experts=m.expert_rows, top_k=m.top_k,
            capacity_factor=m.capacity_factor)
        idx, _, ref_keep = rt.route(m, x, router)
    assert C == m.capacity(T)
    flat_e = np.asarray(idx).reshape(-1)
    order = np.argsort(flat_e, kind="stable")
    program_keep = np.zeros(T * m.top_k, bool)
    program_keep[order] = np.asarray(keep)
    assert np.array_equal(np.asarray(st), np.repeat(np.arange(T), m.top_k)[order])
    assert np.array_equal(program_keep, np.asarray(ref_keep).reshape(-1))
    assert 0 < (~program_keep).sum() < T


@pytest.fixture(scope="module")
def followed(tiny):
    cell, m = tiny
    opt = rt.Optimizer.of(cell.config, cell.traffic["num_steps"])
    rows = [_rows(cell, 10 + i) for i in range(3)]
    return cell, m, opt, rows, rt.follow(m, opt, SEED, rows)


def test_reference_steps_move_every_leaf(followed):
    *_, ref = followed
    assert len(ref["losses"]) == 3
    assert all(abs(x - np.log(512)) < 0.1 for x in ref["losses"])  # near uniform at init
    assert all(v > 0 for v in ref["grad_norms"].values())
    assert all(v > 0 for v in ref["change_norms"].values())
    assert rt.readings(ref, ref) == {"loss_gap": 0.0, "grad_gap": 0.0, "change_gap": 0.0}


@pytest.mark.parametrize("control", ["fp8", "top_k_minus_one"])
def test_controls_fail_the_limits(control, followed):
    import dataclasses

    cell, m, opt, rows, ref = followed
    if control == "fp8":
        run = rt.follow(m, opt, SEED, rows, quant="fp8")
    else:
        run = rt.follow(dataclasses.replace(m, top_k=m.top_k - 1), opt, SEED, rows)
    got = rt.readings(run, ref)
    limits = cell.traffic["limits"]
    assert any(got[k] > limits[k] for k in limits), got
