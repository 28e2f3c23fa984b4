"""Share (%) of the chunk forward's rows that were prefilling: the
program's ``chunk_rows`` over ``chunk_iters`` times the slots, over the
window. The forward computes every slot's row at each of those
iterations."""

import metrics_common as mc


def read(record):
    rows = mc.program_counter_delta(record, "chunk_rows")
    iters = mc.program_counter_delta(record, "chunk_iters")
    if rows is None or not iters:
        return None
    return 100.0 * rows / (iters * record["n_slots"])
