"""Mean device idle gap between consecutive device programs inside one
recommend() call."""

from chipbench import trace_reduce


def read(record):
    tr = record.get("trace")
    gaps = trace_reduce.program_gaps_in_spans_ns(tr, "chipbench.recommend") if tr else []
    return sum(gaps) / len(gaps) / 1e3 if gaps else None
