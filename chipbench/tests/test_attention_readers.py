"""The attention layer's readers, ``attention_share.train`` and
``attention_flash_share.train``: on one step of the hybrid cell recorded on
a TPU v5e before the flash kernel (attention under ``repro.attention``
alone), on steps by hand that hold the kernel's scope, and on a record
without a trace."""

import pathlib

import pytest

from chipbench import op_scopes
from chipbench import run as harness
from chipbench import trace_reduce as tr

HERE = pathlib.Path(__file__).parent
METRICS = HERE.parent / "metrics"
NAMES = ("attention_share.train", "attention_flash_share.train")


def _reader(name):
    return harness.load_module(METRICS / f"{name}.py", name).read


@pytest.fixture
def recorded(monkeypatch):
    """``op_scopes.of`` reading the recorded step slice of the hybrid cell."""
    from jax.profiler import ProfileData

    raw = ProfileData.text_proto_to_serialized_xspace(
        (HERE / "fixtures" / "hybrid_step_slice.textproto").read_text())
    times = op_scopes.times(*op_scopes.from_xspace(raw))
    monkeypatch.setattr(op_scopes, "of", lambda record: times)


def test_attention_share_of_a_step_recorded_on_a_tpu(recorded):
    assert _reader("attention_share.train")({"trace": None}) == pytest.approx(
        100.0 * 5236286.593999982 / 19999903.312000036)


def test_flash_share_reads_0_where_attention_ran_without_the_kernel(recorded):
    assert _reader("attention_flash_share.train")({"trace": None}) == 0.0


# One chip, window [0, 200] ns, steps [0, 100] and [100, 200]; busy 160 ns:
# attention's projections 20 ns, the kernel 60 ns (a forward [20, 50]
# and a backward [120, 150]), the rest 80 ns.
STEPS = tr.Trace(
    ops={"/device:TPU:0": []}, programs={"/device:TPU:0": []},
    spans=[(0, 200, tr.WINDOW_SPAN), (0, 100, op_scopes.STEP_SPAN),
           (100, 200, op_scopes.STEP_SPAN)])
SCOPED = {"/device:TPU:0": [
    (0, 10, "repro.attention"), (20, 50, "repro.attention.flash"), (50, 90, "repro.mlp"),
    (110, 120, "repro.attention"), (120, 150, "repro.attention.flash"), (150, 190, None)]}


@pytest.mark.parametrize("name, want", [("attention_share.train", 100.0 * 80 / 160),
                                        ("attention_flash_share.train", 100.0 * 60 / 80)])
def test_readers_with_the_kernel_by_hand(monkeypatch, name, want):
    monkeypatch.setattr(op_scopes, "of", lambda record: op_scopes.times(STEPS, SCOPED))
    assert _reader(name)({"trace": STEPS}) == pytest.approx(want)


@pytest.mark.parametrize("name", NAMES)
def test_readers_read_none_without_a_trace_or_an_attention_scope(monkeypatch, name):
    read = _reader(name)
    assert read({}) is None
    no_attention = {"/device:TPU:0": [(0, 90, "repro.mlp"), (110, 190, None)]}
    monkeypatch.setattr(op_scopes, "of", lambda record: op_scopes.times(STEPS, no_attention))
    assert read({"trace": STEPS}) is None
