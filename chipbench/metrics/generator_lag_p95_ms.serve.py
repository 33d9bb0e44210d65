"""95th percentile of how late the load generator sent a window request."""


def read(record):
    v = record.get("lag_p95_s")
    return None if v is None else v * 1e3
