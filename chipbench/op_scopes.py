"""Device time of the program's named scopes in the traced training steps.

The program names parts of its work with ``jax.named_scope``: names that
start with ``repro.``, such as ``repro.mamba2.ssd`` (``models/mamba2.py``).
The compiler keeps each operation's JAX name stack in its metadata, and the
TPU profiler hands it on as the stat ``tf_op`` of the event metadata that
the events of one operation on a device's ``XLA Ops`` line share (so on a
TPU v5e; an event's own stat of that name is read first).  A stack reads,
e.g., ``jit(step_fn)/jvp()/while/body/closed_call/repro.mamba2.ssd/neg:``;
the backward pass keeps the forward's names under ``transpose(jvp())``.
``jax.profiler.ProfileData`` shows events' own stats only, so the profile
is parsed with the XSpace message classes that TensorFlow ships.  An
operation's scope is the innermost ``repro.`` name in its stack.  A fusion
counts under the scope of its root operation, whose metadata the fused
operation keeps; a loop counts under the scope it was written in and the
operations inside it under their own; an operation outside every
``repro.`` scope counts under none.

``of(record)`` reads the profile that ``run.py``'s tracer wrote again (the
newest ``.xplane.pb`` under ``.chipbench/trace``), checks that it is the
record's own (the same window), and sums device time inside the traced
``chipbench.train.step`` spans, per chip and averaged over chips: ``BUSY``,
the union of every operation's interval, and for each scope the union of
its operations' intervals.  ``STEPS`` is the number of those step spans.  A
program that names no scope, or a profile without the stat, gives no
scope, and the readers then read ``None``.
"""

from __future__ import annotations

import functools
import pathlib
import re
from typing import Dict, List, Optional, Tuple

from chipbench import trace_reduce
from chipbench.trace_reduce import clip, intersect_total, union

TRACE_DIR = pathlib.Path(__file__).resolve().parent.parent / ".chipbench" / "trace"
NAME_STAT = "tf_op"
STEP_SPAN = "chipbench.train.step"
BUSY = "busy"
STEPS = "steps"
_SCOPE = re.compile(r"repro\.[A-Za-z0-9_.]*[A-Za-z0-9_]")

ScopedEvent = Tuple[float, float, Optional[str]]


def scope_of(stack: str) -> Optional[str]:
    """The innermost ``repro.`` scope named in a name stack, or ``None``."""
    found = _SCOPE.findall(stack)
    return found[-1] if found else None


def times(trace: trace_reduce.Trace, ops: Dict[str, List[ScopedEvent]]) -> Dict[str, float]:
    """Device time (ns) inside the window's step spans: ``BUSY`` and one
    entry per scope, averaged over chips, and ``STEPS``."""
    lo, hi = trace.window
    steps = [(s, e) for s, e, n in trace.spans if n == STEP_SPAN and s >= lo and e <= hi]
    cover = union(steps)
    out: Dict[str, float] = {STEPS: float(len(steps))}
    if not cover or not ops:
        return out
    sums: Dict[str, float] = {}
    for events in ops.values():
        by_scope: Dict[str, list] = {BUSY: []}
        for s, e, scope in events:
            by_scope[BUSY].append((s, e))
            if scope is not None:
                by_scope.setdefault(scope, []).append((s, e))
        for scope, ivs in by_scope.items():
            busy = intersect_total(clip(union(ivs), lo, hi), cover)
            sums[scope] = sums.get(scope, 0.0) + busy
    out.update({k: v / len(ops) for k, v in sums.items()})
    return out


def _xplane_pb2():
    """The XSpace message classes that the installed TensorFlow ships
    (``tsl/profiler/protobuf/xplane_pb2.py``), loaded by path without
    importing TensorFlow; ``None`` where there are none."""
    import importlib.util

    spec = importlib.util.find_spec("tensorflow")
    if spec is None or not spec.submodule_search_locations:
        return None
    path = pathlib.Path(list(spec.submodule_search_locations)[0],
                        "tsl", "profiler", "protobuf", "xplane_pb2.py")
    if not path.exists():
        return None
    mod_spec = importlib.util.spec_from_file_location("chipbench_xplane_pb2", path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod


def scoped_ops(raw: bytes) -> Dict[str, List[ScopedEvent]]:
    """Per device plane of a serialized profile, each ``XLA Ops`` event as
    (start, end, scope), its stack read from the event's own stats or else
    from its event metadata's."""
    pb2 = _xplane_pb2()
    if pb2 is None:
        return {}
    space = pb2.XSpace()
    space.ParseFromString(raw)
    out: Dict[str, List[ScopedEvent]] = {}
    for plane in space.planes:
        if not plane.name.startswith(trace_reduce.DEVICE_PLANE):
            continue
        names = {k: v.name for k, v in plane.stat_metadata.items()}

        def stack(stats):
            for st in stats:
                if names.get(st.metadata_id) == NAME_STAT:
                    return st.str_value or names.get(st.ref_value, "")
            return None

        meta = {k: stack(v.stats) for k, v in plane.event_metadata.items()}
        for line in plane.lines:
            if line.name != trace_reduce.OPS_LINE:
                continue
            for e in line.events:
                s = line.timestamp_ns + e.offset_ps / 1000.0
                st = stack(e.stats) or meta.get(e.metadata_id) or ""
                out.setdefault(plane.name, []).append(
                    (s, s + e.duration_ps / 1000.0, scope_of(st)))
    return out


def from_xspace(raw: bytes) -> Tuple[trace_reduce.Trace, Dict[str, List[ScopedEvent]]]:
    """A serialized profile (``.xplane.pb``) reduced to ``trace_reduce``'s
    trace and each device operation with its scope."""
    from jax.profiler import ProfileData

    return trace_reduce.from_profile(ProfileData.from_serialized_xspace(raw)), scoped_ops(raw)


@functools.lru_cache(maxsize=2)
def _load_file(path: str, mtime_ns: int):
    return from_xspace(pathlib.Path(path).read_bytes())


def of(record) -> Optional[Dict[str, float]]:
    """``times`` of a traced run's steps, from its profile in ``TRACE_DIR``;
    ``None`` when the run was not traced or that profile is not at hand."""
    trace = record.get("trace")
    if trace is None:
        return None
    files = sorted(TRACE_DIR.rglob("*.xplane.pb"), key=lambda p: p.stat().st_mtime)
    if not files:
        return None
    full, ops = _load_file(str(files[-1]), files[-1].stat().st_mtime_ns)
    if full.window != trace.window:
        return None
    return times(trace, ops)
