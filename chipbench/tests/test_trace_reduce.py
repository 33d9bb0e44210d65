"""The reduction from trace to metrics, on hand-made traces (reduced ones,
and an XSpace proto written as the profiler writes it) and on one call
traced on a TPU v5e."""

import json
import pathlib

import pytest

from chipbench import trace_reduce as tr

# One chip, a 100 ns window [0, 100].  Ops: [10, 30] and [20, 40] overlap
# (busy 10..40), [60, 70], and [95, 120] runs past the window's end.
# Programs: [10, 40], [45, 50], [60, 70] inside one recommend span [5, 80].
HAND = tr.Trace(
    ops={"/device:TPU:0": [(10, 30, "fusion"), (20, 40, "gbt_predict"),
                           (60, 70, "fusion"), (95, 120, "copy")]},
    programs={"/device:TPU:0": [(10, 40, "jit_a"), (45, 50, "jit_b"),
                                (60, 70, "jit_a"), (95, 120, "jit_c")]},
    spans=[(0, 100, tr.WINDOW_SPAN), (5, 80, "chipbench.recommend"),
           (40, 60, "chipbench.inner")],
)


def test_busy_and_idle_share_by_hand():
    # busy: [10, 40] + [60, 70] + [95, 100] = 30 + 10 + 5
    assert tr.busy_ns(HAND) == 45
    assert tr.idle_share(HAND) == pytest.approx(0.55)


def test_busy_inside_spans_by_hand():
    # span [5, 80] meets busy [10, 40] and [60, 70]
    assert tr.busy_in_spans_ns(HAND, "chipbench.recommend") == 40
    assert tr.busy_in_spans_ns(HAND, "chipbench.absent") is None


def test_program_gaps_inside_spans_by_hand():
    # programs inside [5, 80]: [10, 40], [45, 50], [60, 70] -> gaps 5 and 10
    assert sorted(tr.program_gaps_in_spans_ns(HAND, "chipbench.recommend")) == [5, 10]


def test_breakdown_by_hand():
    b = tr.breakdown(HAND)
    # fusion 20 + 10, gbt_predict 20, copy clipped to 5
    assert b["device_ops"] == [["fusion", 30e-9], ["gbt_predict", 20e-9], ["copy", 5e-9]]
    # gaps of chip 0, longest first: [70, 95] (middle 82.5, after the
    # recommend span), [40, 60] (the inner span open), [0, 10] (middle 5)
    assert b["idle_gaps"] == [["outside any span", 25e-9],
                              ["chipbench.inner", 20e-9],
                              ["chipbench.recommend", 10e-9]]


def test_no_device_reads_nothing():
    t = tr.Trace(ops={}, programs={}, spans=[(0, 10, tr.WINDOW_SPAN)])
    assert tr.busy_ns(t) is None and tr.idle_share(t) is None
    assert tr.breakdown(t) == {"device_ops": [], "idle_gaps": []}


def test_a_trace_without_the_window_span_is_an_error():
    with pytest.raises(ValueError):
        tr.Trace(ops={}, programs={}, spans=[]).window


def test_union_and_intersection():
    assert tr.union([(5, 6), (1, 3), (2, 4)]) == [(1, 4), (5, 6)]
    assert tr.intersect_total([(0, 10), (20, 30)], [(5, 25)]) == 10


XSPACE = """
planes {
  id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 1000
          events { metadata_id: 1 offset_ps: 0 duration_ps: 2000000 }
          events { metadata_id: 2 offset_ps: 3000000 duration_ps: 1000000 } }
  lines { id: 2 name: "XLA Modules" timestamp_ns: 1000
          events { metadata_id: 3 offset_ps: 0 duration_ps: 4000000 } }
  event_metadata { key: 1 value { id: 1 name: "fusion.1" } }
  event_metadata { key: 2 value { id: 2 name: "gbt_predict" } }
  event_metadata { key: 3 value { id: 3 name: "jit_score" } }
}
planes {
  id: 2 name: "/host:CPU"
  lines { id: 7 name: "python" timestamp_ns: 1000
          events { metadata_id: 1 offset_ps: 0 duration_ps: 10000000 }
          events { metadata_id: 2 offset_ps: 0 duration_ps: 5000000 }
          events { metadata_id: 3 offset_ps: 0 duration_ps: 5000000 } }
  event_metadata { key: 1 value { id: 1 name: "chipbench.window" } }
  event_metadata { key: 2 value { id: 2 name: "chipbench.recommend" } }
  event_metadata { key: 3 value { id: 3 name: "PjitFunction(score)" } }
}
"""


def test_load_from_an_xspace():
    from jax.profiler import ProfileData

    t = tr.from_profile(ProfileData.from_text_proto(XSPACE))
    assert t.ops == {"/device:TPU:0": [(1000.0, 3000.0, "fusion.1"),
                                       (4000.0, 5000.0, "gbt_predict")]}
    assert t.programs == {"/device:TPU:0": [(1000.0, 5000.0, "jit_score")]}
    # only the benchmark's own spans are kept
    assert [n for _, _, n in t.spans] == ["chipbench.recommend", "chipbench.window"]
    assert t.window_ns == 10000
    assert tr.busy_ns(t) == 3000
    assert tr.busy_in_spans_ns(t, "chipbench.recommend") == 3000


def test_json_round_trip(tmp_path):
    tr.save_json(HAND, tmp_path / "t.json")
    back = tr.load_json(tmp_path / "t.json")
    assert back.to_json() == tr.Trace.from_json(HAND.to_json()).to_json()
    assert tr.busy_ns(back) == 45


FIXTURES = pathlib.Path(__file__).parent / "fixtures"


def test_a_recommend_call_traced_on_the_chip():
    """One recommend() call over the 10^6 grid as the profiler traced it on
    a TPU v5e, cut by time from a traced run (the window span clipped to the
    call), with what the reduction read from it there."""
    from jax.profiler import ProfileData

    want = json.loads((FIXTURES / "mega_one_call.expected.json").read_text())
    t = tr.from_profile(ProfileData.from_text_proto(
        (FIXTURES / "mega_one_call.textproto").read_text()))
    assert t.window_ns == want["window_ns"]
    assert tr.busy_ns(t) == want["busy_ns"]
    assert tr.busy_in_spans_ns(t, "chipbench.recommend") == want["busy_in_recommend_ns"]
    gaps = tr.program_gaps_in_spans_ns(t, "chipbench.recommend")
    assert (len(gaps), sum(gaps)) == (want["program_gaps"], want["gap_sum_ns"])
    assert json.loads(json.dumps(tr.breakdown(t))) == want["breakdown"]
    # 123 chunk programs and a few more; the kernel is most of the busy time
    assert 123 <= len(t.programs["/device:TPU:0"]) < 140
    name, secs = tr.breakdown(t)["device_ops"][0]
    assert "gbt_predict" in name and secs > 0.9 * tr.busy_ns(t) / 1e9
    assert 0 < tr.busy_ns(t) < t.window_ns
