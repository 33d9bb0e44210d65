"""Median duration of the window's recommend() calls, on the host's clock.

A steadier reading of the same work as ``candidates_per_s``: the host of a
one-chip machine now and then stands still for 0.1-2 s, which lengthens a
few calls and moves the window's rate, but not the median call.
"""

import statistics


def read(record):
    calls = record.get("call_s")
    return 1e3 * statistics.median(calls) if calls else None
