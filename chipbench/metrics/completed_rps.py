"""Requests answered 200 within the window, over the window's seconds."""


def read(record):
    if "completed_200" not in record:
        return None
    return record["completed_200"] / record["window_s"]
