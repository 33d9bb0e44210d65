"""Median interval of the window's training steps, on the host's clock.

A steadier reading of the same work as ``train_tokens_per_s``: a host that
stands still lengthens a few steps and moves the window's rate, but not
the median step.
"""

import statistics


def read(record):
    steps = record.get("step_s")
    return 1e3 * statistics.median(steps) if steps else None
