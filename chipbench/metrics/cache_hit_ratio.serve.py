"""Response-cache hits over lookups in the window (/stats deltas)."""


def read(record):
    s = record.get("stats")
    if not s or s["hits"] + s["misses"] == 0:
        return None
    return 100.0 * s["hits"] / (s["hits"] + s["misses"])
