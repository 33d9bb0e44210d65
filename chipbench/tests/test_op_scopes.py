"""Device time by the program's named scopes, read from a profile as the
TPU profiler writes it: by hand in both places the profiler can keep an
operation's name stack (on the event, or on the event metadata its events
share), and on one step of the hybrid cell recorded on a TPU v5e."""

import pytest

from chipbench import op_scopes

# Window [0, 200] ns, steps [10, 100] and [100, 190].  Ops: a loop [20, 90]
# outside every scope holding an SSD fusion [30, 50] (backward) and a conv
# fusion [50, 60]; an SSD fusion [110, 150] and an attention one [150, 170].
STACKS = {1: "jit(step_fn)/while", 2: "jit(step_fn)/transpose(jvp(repro.mamba2.ssd))/dot_general",
          3: "jit(step_fn)/repro.mamba2.conv/add", 4: "jit(step_fn)/jvp(repro.mamba2.ssd)/exp",
          5: "jit(step_fn)/repro.attention/dot_general"}
OPS = [(1, 20, 90), (2, 30, 50), (3, 50, 60), (4, 110, 150), (5, 150, 170)]
HOST = '''planes {
  id: 1 name: "/host:CPU"
  lines { id: 1 name: "python" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 200000 }
    events { metadata_id: 2 offset_ps: 10000 duration_ps: 90000 }
    events { metadata_id: 2 offset_ps: 100000 duration_ps: 90000 } }
  event_metadata { key: 1 value { id: 1 name: "chipbench.window" } }
  event_metadata { key: 2 value { id: 2 name: "chipbench.train.step" } }
}
'''
WANT = {op_scopes.STEPS: 2.0, op_scopes.BUSY: 130.0, "repro.mamba2.ssd": 60.0,
        "repro.mamba2.conv": 10.0, "repro.attention": 20.0}


def _device(on_events: bool) -> str:
    events = "".join(
        f"events {{ metadata_id: {k} offset_ps: {s * 1000} duration_ps: {(e - s) * 1000}"
        + (f' stats {{ metadata_id: 100 str_value: "{STACKS[k]}" }}' if on_events else "")
        + " } " for k, s, e in OPS)
    meta = "".join(
        f'event_metadata {{ key: {k} value {{ id: {k} name: "fusion.{k}"'
        + ("" if on_events else f" stats {{ metadata_id: 100 ref_value: {100 + k} }}")
        + " } } " for k in STACKS)
    names = "".join(f'stat_metadata {{ key: {100 + k} value {{ id: {100 + k} name: "{v}" }} }} '
                    for k, v in STACKS.items())
    return (f'planes {{ id: 2 name: "/device:TPU:0" '
            f'lines {{ id: 1 name: "XLA Ops" timestamp_ns: 0 {events}}} {meta}'
            f'stat_metadata {{ key: 100 value {{ id: 100 name: "tf_op" }} }} {names}}}\n')


@pytest.mark.parametrize("on_events", [True, False], ids=["on_events", "on_metadata"])
def test_scopes_by_hand(on_events):
    from jax.profiler import ProfileData

    raw = ProfileData.text_proto_to_serialized_xspace(HOST + _device(on_events))
    trace, ops = op_scopes.from_xspace(raw)
    assert op_scopes.times(trace, ops) == WANT


def test_scopes_of_a_step_recorded_on_a_tpu():
    """20 ms of one traced step of the hybrid cell on a TPU v5e (193 device
    operations, their name stacks on the event metadata, as the profiler
    wrote them; names of the operations shortened), with the window and the
    step span cut to that slice."""
    import json
    import pathlib

    from jax.profiler import ProfileData

    from chipbench import trace_reduce

    fixtures = pathlib.Path(__file__).parent / "fixtures"
    raw = ProfileData.text_proto_to_serialized_xspace(
        (fixtures / "hybrid_step_slice.textproto").read_text())
    trace, ops = op_scopes.from_xspace(raw)
    t = op_scopes.times(trace, ops)
    assert t == pytest.approx(json.loads((fixtures / "hybrid_step_slice.expected.json").read_text()))
    # every layer's scope is there, each within the busy time, which is
    # trace_reduce's own reading of the same operations
    assert set(t) - {op_scopes.STEPS, op_scopes.BUSY} == {
        "repro.mamba2.in_proj", "repro.mamba2.conv", "repro.mamba2.ssd", "repro.mamba2.norm",
        "repro.mamba2.out_proj", "repro.attention", "repro.mlp"}
    assert t[op_scopes.BUSY] == pytest.approx(trace_reduce.busy_ns(trace), rel=1e-5)
    assert sum(v for k, v in t.items() if k.startswith("repro.")) <= t[op_scopes.BUSY]
