"""Tokens of the window's training steps over the window's seconds."""


def read(record):
    if "train_tokens" not in record:
        return None
    return record["train_tokens"] / record["window_s"]
