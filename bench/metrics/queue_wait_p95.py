"""95th percentile of the seconds from submission to first admission,
over the requests due in the window, from the program's own stamps
(``SLOScheduler.request_stamps()``, on ``time.monotonic()``); one not
admitted by the window's end counts at its wait so far, as
``ttft_p95`` counts a missing first token."""

import numpy as np


def read(record):
    stamps = record.get("request_stamps")
    if stamps is None:
        return None
    w0, w1 = record["window"]
    end = w1 - record["monotonic_offset"]      # on the program's clock
    due = {cl.rid for cl in record["clients"] if w0 <= cl.due < w1}
    waits = [min(end if s["admitted"] is None else s["admitted"], end)
             - s["arrival"] for s in stamps if s["request_id"] in due]
    return float(np.percentile(waits, 95)) if waits else None
