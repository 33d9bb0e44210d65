"""The program's own host spans, read beside the benchmark's.

``trace_reduce`` keeps the host spans the benchmark opened (``chipbench.``).
The program opens spans of its own around the phases of its work, with names
that start with ``repro.`` (``repro.core.autotune.RECOMMEND_SPANS``).
``of(record)`` gives a traced run's ``trace_reduce.Trace`` with those spans
added, read again from the same profile and on the same clock.  The functions
of ``trace_reduce`` read spans by exact name, so they read the same numbers
from it; its ``breakdown`` then names each idle gap by the innermost span of
either kind.  The functions below read the program's spans.

A program that opens no such spans gives a trace without them, and every
function here then reads ``None``.
"""

from __future__ import annotations

import dataclasses
import functools
import pathlib
from typing import List, Optional, Tuple

from chipbench import trace_reduce
from chipbench.trace_reduce import Trace, clip, span_cover, total, union

PREFIX = "repro."
TRACE_DIR = pathlib.Path(__file__).resolve().parent.parent / ".chipbench" / "trace"
"""Where ``run.py``'s tracer writes the profile of a traced run."""


def from_profile(pd) -> Trace:
    """``trace_reduce.from_profile`` with the program's host spans too."""
    base = trace_reduce.from_profile(pd)
    ours = [(float(e.start_ns), float(e.end_ns), e.name)
            for plane in pd.planes if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events if e.name.startswith(PREFIX)]
    return dataclasses.replace(base, spans=sorted(base.spans + ours))


@functools.lru_cache(maxsize=2)
def _load_file(path: str, mtime_ns: int) -> Trace:
    from jax.profiler import ProfileData

    return from_profile(ProfileData.from_file(path))


def load(trace_dir: pathlib.Path) -> Trace:
    """The newest ``.xplane.pb`` under ``trace_dir``, reduced with the
    program's spans (read once per file, however many metrics ask)."""
    files = sorted(pathlib.Path(trace_dir).rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return _load_file(str(files[-1]), files[-1].stat().st_mtime_ns)


def of(record) -> Optional[Trace]:
    """The record's trace with the program's spans: itself when it holds
    them already, else read again from the profile in ``TRACE_DIR``, which
    has to be the one it came from (the same window).  ``None`` when the
    run was not traced or that profile is not at hand."""
    trace = record.get("trace")
    if trace is None or any(n.startswith(PREFIX) for *_, n in trace.spans):
        return trace
    try:
        full = load(TRACE_DIR)
    except FileNotFoundError:
        return None
    return full if full.window == trace.window else None


# -- the numbers ----------------------------------------------------------------

def intersect(a, b) -> List[Tuple[float, float]]:
    """Intersection of two disjoint sorted interval lists."""
    out: List[Tuple[float, float]] = []
    i = j = 0
    while i < len(a) and j < len(b):
        lo = max(a[i][0], b[j][0])
        hi = min(a[i][1], b[j][1])
        if hi > lo:
            out.append((lo, hi))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def prefix_cover(trace: Trace, prefix: str) -> List[Tuple[float, float]]:
    """Union of the spans whose names start with ``prefix``, clipped to the
    window."""
    lo, hi = trace.window
    return clip(union([(s, e) for s, e, n in trace.spans if n.startswith(prefix)]),
                lo, hi)


def span_mean_ns(trace: Trace, name: str) -> Optional[float]:
    """Mean duration of the spans named ``name`` that lie wholly inside the
    window.  ``None`` when there are none."""
    lo, hi = trace.window
    durations = [e - s for s, e, n in trace.spans if n == name and s >= lo and e <= hi]
    return sum(durations) / len(durations) if durations else None


def idle_in_spans_ns(trace: Trace, name: str, prefix: str = "") -> Optional[float]:
    """Device idle time inside host spans named ``name`` that spans whose
    names start with ``prefix`` also cover (all of it, for the empty prefix),
    averaged over chips.  ``None`` when no span named ``name``, or none under
    ``prefix``, is in the window, or no chip ran an operation."""
    cover = span_cover(trace, name)
    if prefix:
        cover = intersect(cover, prefix_cover(trace, prefix))
    if not cover or not trace.ops:
        return None
    lo, hi = trace.window
    idle = [total(cover) - total(intersect(clip(union([(s, e) for s, e, _ in ops]), lo, hi),
                                           cover))
            for ops in trace.ops.values()]
    return sum(idle) / len(idle)
