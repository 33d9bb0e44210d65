"""What decides ``correct`` for the training cell, at a size a CPU test run
holds: a sound run passes, and a run whose timed path is broken underneath
fails, once for each fault a training cell on one chip can have.  Each
drives the whole run of the cell except the harness's look for a chip."""

import pytest

from chipbench.tests import tiny_train


def test_sound_run_is_correct():
    cell, record, result = tiny_train.run()
    assert result["correct"], (record["errors"], result["checks"])
    assert result["attempted"] > 0 and result["failed"] == 0
    assert set(result["checks"]) == set(cell.traffic["limits"])
    assert list(result)[-1] == "checks"
    assert record["train_tokens"] == result["attempted"] * 4 * 32
    assert len(record["step_s"]) == result["attempted"]


def _state_unchanged(monkeypatch):
    import jax
    import jax.numpy as jnp

    from repro.train import trainer as trainer_mod

    real_init = trainer_mod.Trainer.__init__

    def init(self, *a, **kw):
        real_init(self, *a, **kw)
        step = self._step

        def unchanged(state, batch):
            _, metrics = step(jax.tree.map(jnp.copy, state), batch)
            return state, metrics

        self._step = unchanged

    monkeypatch.setattr(trainer_mod.Trainer, "__init__", init)


def _half_batch(monkeypatch):
    from repro.train import trainer as trainer_mod

    real = trainer_mod.Trainer._default_make_batch

    def half(self, tokens):
        return real(self, tokens[: tokens.shape[0] // 2])

    monkeypatch.setattr(trainer_mod.Trainer, "_default_make_batch", half)


def _update_altered(monkeypatch):
    import repro.optim as optim

    real = optim.adamw_update

    def doubled(grads, params, mu, nu, step, cfg, lr_scale=1.0):
        new, m, v, om = real(grads, params, mu, nu, step, cfg, lr_scale)
        old_r = params["blocks"]["moe"]["router"]
        new["blocks"]["moe"]["router"] = 2 * new["blocks"]["moe"]["router"] - old_r
        return new, m, v, om

    monkeypatch.setattr(optim, "adamw_update", doubled)


@pytest.mark.parametrize("fault", [_state_unchanged, _half_batch, _update_altered],
                         ids=["state_unchanged", "half_batch", "update_altered"])
def test_broken_step_is_not_correct(fault, monkeypatch):
    fault(monkeypatch)
    _, record, result = tiny_train.run()
    assert not result["correct"], result["checks"]
    assert not record["errors"]  # failed by a number compared, not by a crash
