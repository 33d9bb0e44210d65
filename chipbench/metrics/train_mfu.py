"""Model operations of the traced training steps (chipbench/work/train.py)
over their wall time times the chip's peak operation rate."""

from chipbench import trace_reduce
from chipbench.work import peaks


def read(record):
    tr = record.get("trace")
    if tr is None:
        return None
    lo, hi = tr.window
    steps = [(s, e) for s, e, n in tr.spans
             if n == "chipbench.train.step" and s >= lo and e <= hi]
    wall = trace_reduce.total(trace_reduce.union(steps))
    if not wall:
        return None
    ops = len(steps) * record["flops_per_step"]
    return 100.0 * ops / (wall / 1e9) / peaks(record["device_kind"])["flops_per_s"]
