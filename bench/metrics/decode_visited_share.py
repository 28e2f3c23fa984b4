"""Share (%) of the decode kernel's whole grid of pages that it visits:
the program's ``decode_pages`` (the live pages of every slot at each
decode iteration) over ``decode_iters`` times the slots times the
block table's width, over the window."""

import metrics_common as mc


def read(record):
    pages = mc.program_counter_delta(record, "decode_pages")
    iters = mc.program_counter_delta(record, "decode_iters")
    bpr = record.get("blocks_per_row")
    if pages is None or not iters or not bpr:
        return None
    return 100.0 * pages / (iters * record["n_slots"] * bpr)
