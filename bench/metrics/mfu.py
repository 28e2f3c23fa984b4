"""Model FLOPs of the tokens the window processed, over the chip's busy
seconds, as a share (%) of its bf16 peak. Busy rather than wall time:
at a fixed offered rate the wall-clock FLOP rate is the offered load."""

import metrics_common as mc


def read(record):
    tr = record["trace"]
    if tr is None or tr["busy_s"] <= 0:
        return None
    fl = mc.model_flops(record)
    if fl <= 0:
        return None
    return 100.0 * fl / tr["busy_s"] / record["peak"]["bf16_flops_per_s"]
