"""Reduction of a profiler trace to the benchmark's device numbers.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` writes and keeps
three things, all in nanoseconds on the trace's one clock:

- ``ops``: per chip, the device operations (the ``XLA Ops`` line of each
  ``/device:TPU:<n>`` plane), as ``(start, end, name)``;
- ``programs``: per chip, the device programs (the ``XLA Modules`` line);
- ``spans``: the host spans this benchmark opened itself
  (``TraceAnnotation`` names that start with ``chipbench.``).

The traced window is the benchmark's own ``chipbench.window`` span.  Every
number below is clipped to it, taken per chip, and averaged over chips.
``to_json``/``from_json`` keep a trace in that reduced form
(``calibrate.py --dump`` writes one).
"""

from __future__ import annotations

import dataclasses
import json
import pathlib
from typing import Dict, List, Optional, Sequence, Tuple

Event = Tuple[float, float, str]

SPAN_PREFIX = "chipbench."
WINDOW_SPAN = "chipbench.window"
DEVICE_PLANE = "/device:TPU:"
OPS_LINE = "XLA Ops"
PROGRAMS_LINE = "XLA Modules"


@dataclasses.dataclass
class Trace:
    ops: Dict[str, List[Event]]
    programs: Dict[str, List[Event]]
    spans: List[Event]

    @property
    def window(self) -> Tuple[float, float]:
        wins = [(s, e) for s, e, n in self.spans if n == WINDOW_SPAN]
        if not wins:
            raise ValueError(f"trace has no {WINDOW_SPAN} span")
        return min(s for s, _ in wins), max(e for _, e in wins)

    @property
    def window_ns(self) -> float:
        lo, hi = self.window
        return hi - lo

    def to_json(self) -> dict:
        return {"ops": self.ops, "programs": self.programs, "spans": self.spans}

    @classmethod
    def from_json(cls, obj: dict) -> "Trace":
        def events(xs):
            return [(float(s), float(e), str(n)) for s, e, n in xs]

        return cls(ops={k: events(v) for k, v in obj["ops"].items()},
                   programs={k: events(v) for k, v in obj["programs"].items()},
                   spans=events(obj["spans"]))


def _line_events(line) -> List[Event]:
    return [(float(e.start_ns), float(e.end_ns), e.name) for e in line.events]


def from_profile(pd) -> Trace:
    """A ``jax.profiler.ProfileData`` reduced to ops, programs and spans."""
    ops: Dict[str, List[Event]] = {}
    programs: Dict[str, List[Event]] = {}
    spans: List[Event] = []
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PLANE):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops.setdefault(plane.name, []).extend(_line_events(line))
                elif line.name == PROGRAMS_LINE:
                    programs.setdefault(plane.name, []).extend(_line_events(line))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend(ev for ev in _line_events(line)
                             if ev[2].startswith(SPAN_PREFIX))
    for d in (ops, programs):
        for v in d.values():
            v.sort()
    spans.sort()
    return Trace(ops=ops, programs=programs, spans=spans)


def load(trace_dir: pathlib.Path) -> Trace:
    """The newest ``.xplane.pb`` under ``trace_dir``, reduced."""
    from jax.profiler import ProfileData

    files = sorted(pathlib.Path(trace_dir).rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return from_profile(ProfileData.from_file(str(files[-1])))


# -- interval arithmetic ------------------------------------------------------

def union(intervals: Sequence[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """Sorted, disjoint cover of ``intervals``."""
    out: List[List[float]] = []
    for s, e in sorted((s, e) for s, e in intervals if e > s):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals, lo: float, hi: float) -> List[Tuple[float, float]]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals if e > lo and s < hi]


def total(intervals) -> float:
    return sum(e - s for s, e in intervals)


def intersect_total(a, b) -> float:
    """Length of the intersection of two disjoint sorted interval lists."""
    i = j = 0
    out = 0.0
    while i < len(a) and j < len(b):
        lo = max(a[i][0], b[j][0])
        hi = min(a[i][1], b[j][1])
        if hi > lo:
            out += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def _busy(trace: Trace, chip: str) -> List[Tuple[float, float]]:
    lo, hi = trace.window
    return clip(union([(s, e) for s, e, _ in trace.ops.get(chip, [])]), lo, hi)


def span_cover(trace: Trace, name: str) -> List[Tuple[float, float]]:
    lo, hi = trace.window
    return clip(union([(s, e) for s, e, n in trace.spans if n == name]), lo, hi)


def _mean(xs: List[float]) -> Optional[float]:
    return sum(xs) / len(xs) if xs else None


# -- the numbers ----------------------------------------------------------------

def busy_ns(trace: Trace) -> Optional[float]:
    """Device busy time in the window: the union of operation intervals,
    averaged over chips.  ``None`` when no chip ran an operation."""
    return _mean([total(_busy(trace, c)) for c in trace.ops])


def idle_share(trace: Trace) -> Optional[float]:
    busy = busy_ns(trace)
    return None if busy is None else 1.0 - busy / trace.window_ns


def busy_in_spans_ns(trace: Trace, name: str) -> Optional[float]:
    """Device busy time that falls inside host spans named ``name``."""
    cover = span_cover(trace, name)
    if not cover:
        return None
    return _mean([intersect_total(_busy(trace, c), cover) for c in trace.ops])


def program_gaps_in_spans_ns(trace: Trace, name: str) -> List[float]:
    """Idle gaps between consecutive device programs that start and end
    inside one host span named ``name``, over every chip."""
    gaps: List[float] = []
    for lo, hi in span_cover(trace, name):
        for progs in trace.programs.values():
            inside = union([(s, e) for s, e, _ in progs if s >= lo and e <= hi])
            gaps.extend(b[0] - a[1] for a, b in zip(inside, inside[1:]))
    return gaps


def _innermost_span(trace: Trace, t: float) -> str:
    best = None
    for s, e, n in trace.spans:
        if n != WINDOW_SPAN and s <= t <= e and (best is None or e - s < best[1] - best[0]):
            best = (s, e, n)
    return best[2] if best else "outside any span"


def breakdown(trace: Trace, top: int = 10) -> dict:
    """The device operations that took most time in the window (by name,
    summed over chips), and the longest idle gaps of the first chip, each
    named by the innermost benchmark span open at the gap's middle."""
    lo, hi = trace.window
    by_op: Dict[str, float] = {}
    for events in trace.ops.values():
        for s, e, n in events:
            d = min(e, hi) - max(s, lo)
            if d > 0:
                by_op[n] = by_op.get(n, 0.0) + d
    ops = sorted(by_op.items(), key=lambda kv: -kv[1])[:top]
    gaps: List[Tuple[float, float]] = []
    if trace.ops:
        busy = _busy(trace, sorted(trace.ops)[0])
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                if edges[i + 1] > edges[i]]
    gaps.sort(key=lambda g: g[0] - g[1])
    return {
        "device_ops": [[n, d / 1e9] for n, d in ops],
        "idle_gaps": [[_innermost_span(trace, (s + e) / 2), (e - s) / 1e9]
                      for s, e in gaps[:top]],
    }


def save_json(trace: Trace, path: pathlib.Path) -> None:
    pathlib.Path(path).write_text(json.dumps(trace.to_json()))


def load_json(path: pathlib.Path) -> Trace:
    return Trace.from_json(json.loads(pathlib.Path(path).read_text()))
