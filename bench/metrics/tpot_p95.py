"""95th percentile over requests of the mean time per output token."""

import numpy as np

import metrics_common as mc


def read(record):
    x = mc.tpots(record)
    return float(np.percentile(x, 95)) if len(x) else None
