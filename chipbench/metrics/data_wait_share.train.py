"""Share of the traced training steps' wall time spent waiting for input:
in the pipeline's iterator (``chipbench.train.fetch``) or copying the
batch to the device (``chipbench.train.make_batch``)."""

from chipbench import trace_reduce


def read(record):
    tr = record.get("trace")
    wall = trace_reduce.total(trace_reduce.span_cover(tr, "chipbench.train.step")) if tr else 0
    if not wall:
        return None
    wait = trace_reduce.union(trace_reduce.span_cover(tr, "chipbench.train.fetch")
                              + trace_reduce.span_cover(tr, "chipbench.train.make_batch"))
    return 100.0 * trace_reduce.total(wait) / wall
