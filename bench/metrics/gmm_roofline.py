"""Roofline share (%) of the grouped expert matmul kernel (``gmm``):
the least time the window's entries on the experts held here allow
(the program's ``moe_local``; three matmuls per entry) with each held
expert's weights read once per MoE layer call (``chunk_iters`` plus
``decode_iters`` times the MoE layers), over the kernel's summed
device time. The operation and byte counts are the family module's
``gmm_work``."""

import metrics_common as mc
from loader import family


def read(record):
    c = record["config"]
    fam = family(c)
    if not hasattr(fam, "gmm_work"):
        return None
    entries = mc.program_counter_delta(record, "moe_local")
    iters = [mc.program_counter_delta(record, k)
             for k in ("chunk_iters", "decode_iters")]
    if entries is None or None in iters:
        return None
    calls = sum(iters) * fam.sizes(c)["moe_layers"]
    return mc.kernel_share(record, "gmm", [fam.gmm_work(c, entries, calls)])
