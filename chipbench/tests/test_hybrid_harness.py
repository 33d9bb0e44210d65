"""The hybrid training cell: it resolves from BENCHMARK.json, its readers
reduce recorded numbers as written, and what decides ``correct`` passes a
sound run and fails a run whose recurrence is broken underneath, at a size
a CPU test run holds.  Each run drives the whole cell except the
harness's look for a chip."""

import pytest

from chipbench import op_scopes
from chipbench import reference_granite_h as ref
from chipbench import run as harness
from chipbench import trace_reduce as tr
from chipbench.drivers import train as drv
from chipbench.tests import tiny_hybrid

CELL = tiny_hybrid.CELL
# the training cells' readers, which read the record keys both drivers
# fill, and the SSD's own
SHARED = {"train_step_ms_p50", "data_wait_share.train", "device_idle_share.train",
          "train_mfu"}
OWN = {"ssd_share.hybrid", "ssd_roofline.hybrid"}
READERS = {"setup_s", "train_tokens_per_s"} | SHARED | OWN


def test_hybrid_cell_resolves_every_file():
    cell = harness.resolve(CELL)
    assert cell.chips == 1 and cell.traffic["driver"] == "train_hybrid"
    assert cell.config["name"] == "granite-4.0-h-micro"
    assert {m["name"] for m in cell.end_to_end} == {"setup_s", "train_tokens_per_s"}
    assert set(cell.readers) == READERS
    assert {m["moves"] for m in cell.per_layer} == {"train_tokens_per_s"}
    assert set(cell.traffic["limits"]) == set(cell.traffic["limits_why"])
    # the granite-moe cell reads the shared metrics and none of the SSD's
    moe = set(harness.resolve("train-granite-moe-tmpfs").readers)
    assert SHARED <= moe and not moe & OWN


# One chip, window [0, 100] ns: steps [0, 40] and [40, 100], a third past
# the window; the fetch of the second step [36, 40] and its copy [40, 44],
# the first copy [0, 3]; ops busy [5, 35] and [45, 95].
HAND = tr.Trace(
    ops={"/device:TPU:0": [(5, 35, "fusion"), (45, 95, "fusion")]},
    programs={"/device:TPU:0": [(5, 35, "jit_step"), (45, 95, "jit_step")]},
    spans=[(0, 100, tr.WINDOW_SPAN), (0, 40, drv.SPAN_STEP), (40, 100, drv.SPAN_STEP),
           (100, 140, drv.SPAN_STEP), (0, 3, drv.SPAN_BATCH), (36, 40, drv.SPAN_FETCH),
           (40, 44, drv.SPAN_BATCH)],
)
# the ops of HAND with scopes: [5, 35] holds an SSD loop [10, 30] with a
# fusion [12, 20] inside; [45, 95] an SSD op [50, 60] and a conv op [60, 70]
SCOPED = {"/device:TPU:0": [(5, 35, None), (10, 30, "repro.mamba2.ssd"),
                            (12, 20, "repro.mamba2.ssd"), (45, 95, None),
                            (50, 60, "repro.mamba2.ssd"), (60, 70, "repro.mamba2.conv"),
                            (120, 130, "repro.mamba2.ssd")]}


def test_op_scopes_by_hand():
    t = op_scopes.times(HAND, SCOPED)
    assert t == {op_scopes.STEPS: 2.0, op_scopes.BUSY: 80.0,
                 "repro.mamba2.ssd": 30.0, "repro.mamba2.conv": 10.0}
    assert op_scopes.scope_of(
        "jit(step_fn)/transpose(jvp(repro.mamba2.ssd))/dot_general") == "repro.mamba2.ssd"
    assert op_scopes.scope_of("jit(step_fn)/repro.mlp/jvp(repro.attention)/exp") == \
        "repro.attention"
    assert op_scopes.scope_of("jit(step_fn)/while/body/add") is None


def test_trace_readers_by_hand(monkeypatch):
    readers = harness.resolve(CELL).readers
    work = {"ops": 197e12 * 1e-9, "bytes": 819e9 * 3e-9}  # 1 ns of ops, 3 ns of bytes
    record = {"trace": HAND, "flops_per_step": 197e12 * 1e-8, "device_kind": "TPU v5 lite",
              "ssd_work_per_step": work, "step_s": [0.3, 2.0, 0.31]}
    assert readers["train_step_ms_p50"](record) == pytest.approx(310.0)
    assert readers["data_wait_share.train"](record) == pytest.approx(11.0)
    assert readers["device_idle_share.train"](record) == pytest.approx(20.0)
    assert readers["train_mfu"](record) == pytest.approx(20.0)
    assert all(readers[m]({}) is None for m in READERS - {"setup_s", "train_tokens_per_s"})
    monkeypatch.setattr(op_scopes, "of", lambda rec: op_scopes.times(rec["trace"], SCOPED))
    # 30 of 80 busy ns; 2 steps of 3 ns at the roofline in 30 ns
    assert readers["ssd_share.hybrid"](record) == pytest.approx(37.5)
    assert readers["ssd_roofline.hybrid"](record) == pytest.approx(20.0)
    monkeypatch.setattr(op_scopes, "of", lambda rec: op_scopes.times(
        rec["trace"], {"/device:TPU:0": [(5, 35, None)]}))
    # a program without the scope: the readers read nothing
    assert readers["ssd_share.hybrid"](record) is None
    assert readers["ssd_roofline.hybrid"](record) is None


def test_sound_run_is_correct(monkeypatch):
    cell, record, result = tiny_hybrid.run(monkeypatch)
    assert result["correct"], (record["errors"], result["checks"])
    assert result["attempted"] > 0 and result["failed"] == 0
    assert set(result["checks"]) == set(cell.traffic["limits"])
    assert record["train_tokens"] == result["attempted"] * 4 * 32


def _no_state_pass(monkeypatch):
    """The chunked SSD with the pass between chunks dropped: each chunk
    starts from a zero state."""
    from repro.models import mamba2

    real = mamba2.ssd

    def chunks_alone(x, dt, A, Bm, Cm, chunk, unroll=False):
        B, S = x.shape[:2]
        n = S // chunk

        def split(a):
            return a.reshape(B * n, chunk, *a.shape[2:])

        y = real(split(x), split(dt), A, split(Bm), split(Cm), chunk, unroll)
        return y.reshape(x.shape)

    monkeypatch.setattr(mamba2, "ssd", chunks_alone)


def _no_gate(monkeypatch):
    """The gated norm without silu(z)."""
    import jax

    from repro.models import mamba2

    def ungated(cfg, lp, y, z):
        return y * jax.lax.rsqrt((y * y).mean(-1, keepdims=True) + cfg.norm_eps) * lp["norm"]

    monkeypatch.setattr(mamba2, "_gated_norm", ungated)


def _ssd_bf16_operands(monkeypatch):
    """The SSD's products reading bfloat16 operands, as the chip's default
    precision would have them."""
    import jax.numpy as jnp

    from repro.models import mamba2

    class Rounded:
        def __getattr__(self, name):
            return getattr(jnp, name)

        @staticmethod
        def einsum(spec, *ops, precision=None):
            return jnp.einsum(spec, *(o.astype(jnp.bfloat16) for o in ops),
                              preferred_element_type=jnp.result_type(*ops))

    monkeypatch.setattr(mamba2, "jnp", Rounded())


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


@pytest.mark.parametrize("fault", [_no_state_pass, _no_gate, _state_unchanged,
                                   _ssd_bf16_operands],
                         ids=["no_state_pass", "no_gate", "state_unchanged",
                              "ssd_bf16_operands"])
def test_broken_step_is_not_correct(fault, monkeypatch):
    fault(monkeypatch)
    _, record, result = tiny_hybrid.run(monkeypatch)
    assert not result["correct"], result["checks"]
    assert not record["errors"]  # failed by a number compared, not by a crash


def test_each_control_exceeds_a_limit(monkeypatch):
    """A sound run with ``control``: the float8 control and every fault the
    driver reads, each in the program's place, exceed at least one limit."""
    cell, record, result = tiny_hybrid.run(monkeypatch, control=True)
    assert result["correct"], result["checks"]
    limits = cell.traffic["limits"]
    controls = record["control_checks"]
    assert set(controls) == {"fp8", "half_batch", "state_unchanged"} | set(ref.FAULTS)
    for name, readings in controls.items():
        assert set(readings) == set(limits), name
        assert any(readings[k] > limits[k] for k in limits), (name, readings)
    assert controls["state_bf16"]["ssd_gap"] > limits["ssd_gap"]
