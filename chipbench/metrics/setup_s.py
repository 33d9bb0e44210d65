"""Set-up: process start to the start of the window, compiling included."""


def read(record):
    return record["setup_s"]
