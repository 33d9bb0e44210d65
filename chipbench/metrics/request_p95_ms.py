"""95th percentile over every request due in the window, timed from when it
was due; a request not answered 200 counts as beyond any limit."""

import math


def read(record):
    v = record.get("latency_p95_s")
    if v is None:
        return None
    # more than 5% failed: the tail is the generator's whole wait past the close
    return (v if math.isfinite(v) else record["window_s"] + 60.0) * 1e3
