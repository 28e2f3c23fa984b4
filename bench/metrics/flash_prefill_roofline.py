"""Roofline share (%) of the chunk-prefill attention kernel: the least
time the causal attention of the prompt chunks prefilled in the window
allows, over the kernel's summed device time."""

import metrics_common as mc
from loader import family


def read(record):
    c = record["config"]
    fam = family(c)
    C = record["chunk_tokens"]
    work = []
    for cl in mc.prefilled(record):
        for start in range(0, cl.prompt_len, C):
            work.append(fam.flash_prefill_work(
                c, start, min(C, cl.prompt_len - start)))
    return mc.kernel_share(record, "flash_prefill", work)
