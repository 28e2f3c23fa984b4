"""Roofline share (%) of the paged-attention decode kernel: the least
time the K/V reads and FLOPs of the window's decode tokens allow, over
the kernel's summed device time."""

import metrics_common as mc
from loader import family


def read(record):
    c = record["config"]
    fam = family(c)
    work = [fam.paged_attention_work(c, int(x))
            for ctx in mc.decode_contexts(record) for x in ctx]
    return mc.kernel_share(record, "paged_attention", work)
