"""Device busy time of the traced slice per micro-batch scored in it."""

from chipbench import trace_reduce


def read(record):
    tr, s = record.get("trace"), record.get("slice_stats")
    if tr is None or not s or s["n_batches"] == 0:
        return None
    busy = trace_reduce.busy_ns(tr)
    return None if not busy else busy / 1e6 / s["n_batches"]
