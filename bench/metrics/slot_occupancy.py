"""Share (%) of slot-iterations in the window with a decoding slot:
busy slot-steps over slots times loop iterations, from the
scheduler's counters accumulated across its resets."""

import metrics_common as mc


def read(record):
    d = mc.counter_delta(record)
    if d is None or d[0] <= 0:
        return None
    return 100.0 * d[2] / (record["n_slots"] * d[0])
