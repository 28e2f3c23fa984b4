"""95th percentile of time to first token, from each request's due time."""

import numpy as np

import metrics_common as mc


def read(record):
    x = mc.ttfts(record)
    return float(np.percentile(x, 95)) if len(x) else None
