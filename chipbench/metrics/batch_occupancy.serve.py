"""Requests scored per micro-batch in the window (/stats deltas)."""


def read(record):
    s = record.get("stats")
    if not s or s["n_batches"] == 0:
        return None
    return s["n_scored"] / s["n_batches"]
