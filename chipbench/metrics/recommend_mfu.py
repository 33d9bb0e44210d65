"""The traced recommend() calls' operations (chipbench/work/descent.py) over
their wall time times the chip's peak operation rate."""

from chipbench import trace_reduce
from chipbench.work import peaks


def read(record):
    tr = record.get("trace")
    wall = trace_reduce.total(trace_reduce.span_cover(tr, "chipbench.recommend")) if tr else 0
    if not wall:
        return None
    ops = record["traced_calls"] * record["work_per_call"]["ops"]
    return 100.0 * ops / (wall / 1e9) / peaks(record["device_kind"])["flops_per_s"]
