"""Share of the window's scored requests shed (503) or timed out (504)."""


def read(record):
    s = record.get("stats")
    if not s:
        return None
    n = s["predict"] + s["recommend"]
    return None if n == 0 else 100.0 * (s["shed"] + s["deadline_timeouts"]) / n
