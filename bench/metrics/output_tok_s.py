"""Tokens delivered to clients in the window, per second of it."""

import metrics_common as mc


def read(record):
    return mc.window_tokens(record) / record["seconds"]
