"""Seconds from process start to the window's start."""


def read(record):
    return record["setup_s"]
