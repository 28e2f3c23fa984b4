"""Arithmetic the metric readers share, over one run's record.

A record holds the clients (each with its due time, prompt, and the
token events it received, stamped on the benchmark's clock), the
window's edges, the counters logged after every scheduling round, the
configuration and mix, the peaks, and with ``--trace 1`` the reduced
trace. Times are ``time.perf_counter()`` seconds.
"""

from __future__ import annotations

from typing import List, Optional

import numpy as np

import flops
from loader import family


def in_window(record, t) -> bool:
    w0, w1 = record["window"]
    return w0 <= t < w1


def ttfts(record) -> np.ndarray:
    """First-token time minus due time, over requests due in the
    window; one with no first token by the window's end counts at its
    wait so far."""
    w0, w1 = record["window"]
    out = []
    for cl in record["clients"]:
        if not w0 <= cl.due < w1:
            continue
        first = cl.events[0][0] if cl.events else w1
        out.append(min(first, w1) - cl.due)
    return np.asarray(out)


def _tpot(events) -> Optional[float]:
    if len(events) < 2:
        return None
    after = sum(n for _, n in events[1:])
    return (events[-1][0] - events[0][0]) / after


def tpots(record) -> np.ndarray:
    """Per request with two token events or more in the window: the
    time from its first to its last event there, over the tokens that
    arrived after the first event."""
    out = []
    for cl in record["clients"]:
        ev = [e for e in cl.events if in_window(record, e[0])]
        v = _tpot(ev)
        if v is not None:
            out.append(v)
    return np.asarray(out)


def window_tokens(record) -> int:
    return int(sum(n for cl in record["clients"] for t, n in cl.events
                   if in_window(record, t)))


def meets_limits(record, lim: dict) -> float:
    """Share of the requests due in the window whose first token came
    within ``lim["ttft_s"]`` of its due time and whose tokens after the
    first came ``lim["gap_s"]`` apart or less on average (both by the
    window's end)."""
    w0, w1 = record["window"]
    ok = n = 0
    for cl in record["clients"]:
        if not w0 <= cl.due < w1:
            continue
        n += 1
        if not cl.events or cl.events[0][0] - cl.due > lim["ttft_s"]:
            continue
        v = _tpot([e for e in cl.events if e[0] < w1])
        ok += v is None or v <= lim["gap_s"]
    return ok / n if n else 0.0


def backlog_at_end(record) -> int:
    """Requests released before the window's end still without a
    first token at its end."""
    w1 = record["window"][1]
    return sum(1 for cl in record["clients"] if cl.due < w1 and
               (not cl.events or cl.events[0][0] >= w1))


def decode_contexts(record) -> List[np.ndarray]:
    """Per request, the context each of its tokens delivered in the
    window was decoded against. Token 0 comes from the prefill's last
    position; token ``j >= 1`` is made by feeding token ``j - 1`` at
    position ``prompt + j - 1``, which attends over ``prompt + j``."""
    out = []
    for cl in record["clients"]:
        k = 0
        for t, n in cl.events:
            if in_window(record, t):
                j = np.arange(max(k, 1), k + n)
                if len(j):
                    out.append(cl.prompt_len + j)
            k += n
    return out


def prefilled(record):
    """Requests whose first token arrived in the window: their prefill
    ran in it. (No cell shares prompt blocks, so every position of a
    prompt prefills.)"""
    return [cl for cl in record["clients"]
            if cl.events and in_window(record, cl.events[0][0])]


def model_flops(record) -> float:
    c = record["config"]
    fam = family(c)
    total = 0.0
    for ctx in decode_contexts(record):
        total += sum(fam.decode_flops(c, int(x)) for x in ctx)
    for cl in prefilled(record):
        total += fam.prefill_flops(c, 0, cl.prompt_len)
    return total


def counter_delta(record) -> Optional[np.ndarray]:
    """Counters (iterations, prefix-hit blocks, busy slot-steps) over
    the window, from the rounds logged last before each edge."""
    w0, w1 = record["window"]
    log = record["counter_log"]
    before = [c for t, c in log if t <= w0]
    upto = [c for t, c in log if t <= w1]
    if not upto:
        return None
    start = before[-1] if before else np.zeros_like(upto[-1])
    return upto[-1] - start


def kernel_share(record, kernel: str, work) -> Optional[float]:
    """Roofline share in % of ``kernel`` over the window, from its
    summed device time and the (FLOPs, bytes) pairs in ``work``."""
    tr = record["trace"]
    if tr is None:
        return None
    secs = tr["kernels"].get(kernel, 0.0)
    fl = sum(w[0] for w in work)
    by = sum(w[1] for w in work)
    if secs <= 0 or fl <= 0:
        return None
    share, _ = flops.roofline_share(fl, by, secs, record["peak"])
    return share


def program_counter_delta(record, name: str) -> Optional[int]:
    """The program's own lifetime counter ``name`` (a key of
    ``DecodeScheduler.counters()``, logged after every round of a traced
    run) over the window: its value at the last round before the
    window's end less that at the last reading before its start, modulo
    2**32, since the counters are int32 on the device. None where the
    run logged no such counter."""
    w0, w1 = record["window"]
    log = [(t, c[name]) for t, c in record.get("program_counter_log") or []
           if name in c]
    before = [v for t, v in log if t <= w0]
    upto = [v for t, v in log if t <= w1]
    if not before or not upto:
        return None
    return (upto[-1] - before[-1]) % 2**32


def scope_share(record, scope: str) -> Optional[float]:
    """Share (%) of the device's busy time spent in operations whose
    ``op_name`` path holds the component ``scope`` (a
    ``jax.named_scope``, e.g. ``serve.prefill_chunk``). None without a
    trace or where no operation carries the scope."""
    tr = record["trace"]
    if tr is None or tr["busy_s"] <= 0:
        return None
    secs = tr.get("scope_busy_s", {}).get(scope)
    return None if secs is None else 100.0 * secs / tr["busy_s"]
