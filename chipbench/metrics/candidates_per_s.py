"""Candidates scored by the recommend() calls of the window, over its seconds."""


def read(record):
    if "candidates" not in record:
        return None
    return record["candidates"] / record["window_s"]
