"""The descent's share of its roofline in the traced recommend() calls: the
least time the chip needs for their work (chipbench/work/descent.py, bytes
bind) over the device's busy time inside those calls."""

from chipbench import trace_reduce
from chipbench.work import roofline_share


def read(record):
    tr = record.get("trace")
    busy = trace_reduce.busy_in_spans_ns(tr, "chipbench.recommend") if tr else None
    if not busy:
        return None
    n, w = record["traced_calls"], record["work_per_call"]
    return 100.0 * roofline_share(n * w["ops"], n * w["bytes"], busy / 1e9,
                                  record["device_kind"])
