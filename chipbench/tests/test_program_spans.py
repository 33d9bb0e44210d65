"""The program's own spans read beside the benchmark's: hand-made traces, an
XSpace proto written as the profiler writes it, a CPU profile taken by the
harness's tracer, and one call traced on a TPU v5e."""

import json
import pathlib

import pytest

from chipbench import program_spans as ps
from chipbench import trace_reduce as tr

# One chip, window [0, 200], one benchmark call [5, 135] holding the
# program's spans: repro.recommend [6, 125], two chunks of assemble, dispatch
# and fetch, and select [85, 125].  Ops: the two chunks' programs [20, 40]
# and [62, 80], the re-score [100, 104], and [150, 160] after the call.  A
# fetch span [190, 210] runs past the window's end.
PROGRAM = tr.Trace(
    ops={"/device:TPU:0": [(20, 40, "gbt_predict"), (62, 80, "gbt_predict"),
                           (100, 104, "while"), (150, 160, "fusion")]},
    programs={"/device:TPU:0": [(20, 40, "jit_a"), (62, 80, "jit_a"),
                                (100, 104, "jit_b"), (150, 160, "jit_c")]},
    spans=[(0, 200, tr.WINDOW_SPAN), (5, 135, "chipbench.recommend"),
           (6, 125, "repro.recommend"),
           (6, 12, "repro.grid.assemble"), (12, 18, "repro.grid.dispatch"),
           (18, 45, "repro.grid.fetch"),
           (45, 52, "repro.grid.assemble"), (52, 61, "repro.grid.dispatch"),
           (61, 85, "repro.grid.fetch"),
           (85, 125, "repro.recommend.select"), (190, 210, "repro.grid.fetch")],
)
BENCH_ONLY = tr.Trace(ops=PROGRAM.ops, programs=PROGRAM.programs,
                      spans=[ev for ev in PROGRAM.spans if ev[2].startswith("chipbench.")])


def test_span_means_by_hand():
    assert ps.span_mean_ns(PROGRAM, "repro.grid.assemble") == 6.5
    assert ps.span_mean_ns(PROGRAM, "repro.grid.dispatch") == 7.5
    # the fetch past the window's end is left out
    assert ps.span_mean_ns(PROGRAM, "repro.grid.fetch") == 25.5
    assert ps.span_mean_ns(PROGRAM, "repro.recommend.select") == 40
    assert ps.span_mean_ns(PROGRAM, "repro.absent") is None


def test_prefix_cover_and_intersection():
    assert ps.prefix_cover(PROGRAM, "repro.") == [(6, 125), (190, 200)]
    assert ps.prefix_cover(PROGRAM, "repro.grid.d") == [(12, 18), (52, 61)]
    assert ps.intersect([(0, 10), (20, 30)], [(5, 25)]) == [(5, 10), (20, 25)]
    assert tr.total(ps.intersect([(0, 10), (20, 30)], [(5, 25)])) == tr.intersect_total(
        [(0, 10), (20, 30)], [(5, 25)])


def test_idle_covered_by_program_spans_by_hand():
    # the call [5, 135] holds 42 ns of ops: idle 88; the program's spans
    # cover [6, 125] of it: idle 77
    assert ps.idle_in_spans_ns(PROGRAM, "chipbench.recommend") == 88
    assert ps.idle_in_spans_ns(PROGRAM, "chipbench.recommend", "repro.") == 77
    assert ps.idle_in_spans_ns(PROGRAM, "chipbench.recommend", "absent.") is None
    assert ps.idle_in_spans_ns(PROGRAM, "chipbench.absent") is None
    assert ps.idle_in_spans_ns(BENCH_ONLY, "chipbench.recommend", "repro.") is None
    no_ops = tr.Trace(ops={}, programs={}, spans=PROGRAM.spans)
    assert ps.idle_in_spans_ns(no_ops, "chipbench.recommend") is None


def test_breakdown_names_gaps_by_program_spans_by_hand():
    b = tr.breakdown(PROGRAM)
    # gaps, longest first: [104, 150] (middle 127: inside the call, after the
    # program returned, so it keeps the benchmark span's name), [160, 200]
    # (middle 180), [40, 62] (middle 51: the second assemble), [0, 20]
    # (middle 10: the first assemble), [80, 100] (middle 90: select)
    assert b["idle_gaps"] == [["chipbench.recommend", 46e-9],
                              ["outside any span", 40e-9],
                              ["repro.grid.assemble", 22e-9],
                              ["repro.grid.assemble", 20e-9],
                              ["repro.recommend.select", 20e-9]]
    # without the program's spans the same gaps keep the benchmark's names
    assert [n for n, _ in tr.breakdown(BENCH_ONLY)["idle_gaps"]] == [
        "chipbench.recommend", "outside any span", "chipbench.recommend",
        "chipbench.recommend", "chipbench.recommend"]
    # and the benchmark's own numbers do not change
    for t in (PROGRAM, BENCH_ONLY):
        assert tr.busy_in_spans_ns(t, "chipbench.recommend") == 42
        assert sorted(tr.program_gaps_in_spans_ns(t, "chipbench.recommend")) == [20, 22]


XSPACE = """
planes {
  id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 1000
          events { metadata_id: 1 offset_ps: 0 duration_ps: 2000000 } }
  lines { id: 2 name: "XLA Modules" timestamp_ns: 1000
          events { metadata_id: 2 offset_ps: 0 duration_ps: 2000000 } }
  event_metadata { key: 1 value { id: 1 name: "gbt_predict" } }
  event_metadata { key: 2 value { id: 2 name: "jit_score" } }
}
planes {
  id: 2 name: "/host:CPU"
  lines { id: 7 name: "python" timestamp_ns: 1000
          events { metadata_id: 1 offset_ps: 0 duration_ps: 10000000 }
          events { metadata_id: 2 offset_ps: 0 duration_ps: 5000000 }
          events { metadata_id: 3 offset_ps: 1000000 duration_ps: 3000000 }
          events { metadata_id: 4 offset_ps: 0 duration_ps: 5000000 } }
  event_metadata { key: 1 value { id: 1 name: "chipbench.window" } }
  event_metadata { key: 2 value { id: 2 name: "chipbench.recommend" } }
  event_metadata { key: 3 value { id: 3 name: "repro.grid.dispatch" } }
  event_metadata { key: 4 value { id: 4 name: "PjitFunction(score)" } }
}
"""


def test_load_keeps_the_programs_spans():
    from jax.profiler import ProfileData

    pd = ProfileData.from_text_proto(XSPACE)
    t = ps.from_profile(pd)
    assert [n for _, _, n in t.spans] == ["chipbench.recommend", "chipbench.window",
                                          "repro.grid.dispatch"]
    assert ps.span_mean_ns(t, "repro.grid.dispatch") == 3000
    # the benchmark's reduction of the same profile differs by those spans alone
    base = tr.from_profile(pd)
    assert (t.ops, t.programs) == (base.ops, base.programs)
    assert [ev for ev in t.spans if not ev[2].startswith(ps.PREFIX)] == base.spans


def test_of_reads_the_profile_the_record_came_from(tmp_path, monkeypatch):
    """The harness's tracer on the CPU: its record holds the benchmark's
    spans alone, and ``of`` adds the program's from the same profile."""
    import jax
    import jax.numpy as jnp

    from chipbench import run as harness

    tracer = harness.Tracer(tmp_path / "trace")
    tracer.start()
    with jax.profiler.TraceAnnotation("chipbench.recommend"):
        with jax.profiler.TraceAnnotation("repro.grid.dispatch"):
            jnp.arange(8.0).sum().block_until_ready()
    record = {"trace": tracer.stop()}
    assert not any(n.startswith(ps.PREFIX) for *_, n in record["trace"].spans)

    monkeypatch.setattr(ps, "TRACE_DIR", tmp_path / "trace")
    full = ps.of(record)
    assert full.window == record["trace"].window
    assert [n for *_, n in full.spans if n.startswith(ps.PREFIX)] == ["repro.grid.dispatch"]
    assert [ev for ev in full.spans if not ev[2].startswith(ps.PREFIX)] == record["trace"].spans
    # a trace that holds the program's spans already is its own answer
    assert ps.of({"trace": full}) is full
    # a profile of another window, or none, gives nothing
    other = tr.Trace(ops={}, programs={}, spans=[(0.0, 1.0, tr.WINDOW_SPAN)])
    assert ps.of({"trace": other}) is None
    monkeypatch.setattr(ps, "TRACE_DIR", tmp_path / "absent")
    assert ps.of(record) is None
    assert ps.of({}) is None


PROGRAM_METRICS = ("chunk_assemble_us.mega", "chunk_dispatch_us.mega", "chunk_fetch_us.mega",
                   "recommend_select_ms.mega", "idle_attributed_share.mega")
FIXTURES = pathlib.Path(__file__).parent / "fixtures"


def _reader(name):
    from chipbench import run as harness

    return harness.load_module(FIXTURES.parent.parent / "metrics" / f"{name}.py",
                               f"reader_{name}").read


def test_program_span_metrics_by_hand_and_silent_without_program_spans(tmp_path, monkeypatch):
    got = {m: _reader(m)({"trace": PROGRAM}) for m in PROGRAM_METRICS}
    assert got == {"chunk_assemble_us.mega": 6.5e-3, "chunk_dispatch_us.mega": 7.5e-3,
                   "chunk_fetch_us.mega": 25.5e-3, "recommend_select_ms.mega": 40e-6,
                   "idle_attributed_share.mega": pytest.approx(100 * 77 / 88)}
    # a program that opens no spans (as before they existed) reads nothing,
    # whether its profile is at hand or not
    from jax.profiler import ProfileData

    old = ps.from_profile(ProfileData.from_text_proto(
        (FIXTURES / "mega_one_call.textproto").read_text()))
    assert not any(n.startswith(ps.PREFIX) for *_, n in old.spans)
    with monkeypatch.context() as mp:
        mp.setattr(ps, "load", lambda _: old)
        for m in PROGRAM_METRICS:
            assert _reader(m)({"trace": old}) is None
            assert _reader(m)({}) is None
    monkeypatch.setattr(ps, "TRACE_DIR", tmp_path)
    for m in PROGRAM_METRICS:
        assert _reader(m)({"trace": old}) is None


def test_a_recommend_call_with_the_programs_spans_traced_on_the_chip():
    """One recommend() call over the 10^6 grid with the program's spans, as
    the profiler traced it on a TPU v5e: the first traced call of a
    ``--trace 1`` run, cut by time (the window span clipped to the call).
    It reduces to the five metrics the spans feed, with the numbers the
    reduction read when the call was cut; the phases account for the call,
    and the idle gaps are named by the program's spans."""
    from jax.profiler import ProfileData

    want = json.loads((FIXTURES / "mega_one_call_spans.expected.json").read_text())
    pd = ProfileData.from_text_proto((FIXTURES / "mega_one_call_spans.textproto").read_text())
    t = ps.from_profile(pd)
    assert t.window_ns == want["window_ns"]
    got = {m: _reader(m)({"trace": t}) for m in PROGRAM_METRICS}
    assert got == pytest.approx(want["metrics"], rel=1e-12)
    counts = {n: sum(1 for *_, m in t.spans if m == n) for n in want["span_counts"]}
    assert counts == want["span_counts"]
    assert counts["repro.grid.fetch"] == 123 and counts["repro.recommend.select"] == 1
    # 123 chunks of the three phases plus select come within 5% of the call
    phases_ns = 1e3 * 123 * (got["chunk_assemble_us.mega"] + got["chunk_dispatch_us.mega"]
                             + got["chunk_fetch_us.mega"])
    assert phases_ns + 1e6 * got["recommend_select_ms.mega"] == pytest.approx(
        want["call_ns"], rel=0.05)
    assert got["idle_attributed_share.mega"] >= 95
    gaps = json.loads(json.dumps(tr.breakdown(t)["idle_gaps"]))
    assert gaps == want["idle_gaps"]
    assert all(name.startswith(ps.PREFIX) for name, _ in gaps)
    # the benchmark's own reduction of the same call reads the same numbers
    base = tr.from_profile(pd)
    for f in (tr.busy_ns, tr.idle_share):
        assert f(t) == f(base)
    assert (tr.busy_in_spans_ns(t, "chipbench.recommend")
            == tr.busy_in_spans_ns(base, "chipbench.recommend"))
    assert (tr.program_gaps_in_spans_ns(t, "chipbench.recommend")
            == tr.program_gaps_in_spans_ns(base, "chipbench.recommend"))
    assert all(name == "chipbench.recommend" for name, _ in tr.breakdown(base)["idle_gaps"])
