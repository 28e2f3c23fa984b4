"""From a profiler trace to busy time, kernel time and a breakdown.

Two steps, kept apart so that the second can be checked on a small
recorded trace without a chip:

1. ``extract(profile)`` reads a ``jax.profiler.ProfileData`` into plain
   lists: the device's operation events (the "XLA Ops" line of each
   ``/device:TPU:<n>`` plane) and the host's named spans (the
   ``TraceAnnotation`` spans this benchmark writes, ``bench.window``
   among them: the measured window on the trace's own clock).
2. ``reduce(ev, window_s)`` computes the union of the device intervals
   (busy seconds, averaged over the chips), each kernel's summed device
   time (its events found by name), the ten operations that took most
   time (control-flow ops such as ``while``, whose events span their
   bodies, left out), and the ten longest idle gaps labelled by the
   host span that covered most of each.
"""

from __future__ import annotations

import collections
import pathlib
from typing import Dict, List

# kernel -> prefixes of its custom calls' HLO names (the Pallas calls'
# function names: "%paged_attention.9 = bf16[...] custom-call(...)")
KERNELS = {
    "paged_attention": ("%paged_attention",),
    "flash_prefill": ("%flash_prefill",),
}
# control-flow ops whose events span the ops they run
CONTAINERS = ("%while", "%cond", "%conditional", "%call")
OPS_LINE = "XLA Ops"
HOST_SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"    # the measured window, on the trace's clock


def extract(profile) -> dict:
    devices: Dict[str, List[tuple]] = {}
    host: List[tuple] = []
    for plane in profile.planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    devices[plane.name] = [
                        (e.name, e.start_ns, e.duration_ns)
                        for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(HOST_SPAN_PREFIX):
                        host.append((e.name, e.start_ns, e.duration_ns))
    return {"devices": devices, "host": host}


def union(intervals) -> List[tuple]:
    """Merged (start, end) pairs of possibly overlapping intervals."""
    out: List[list] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [tuple(x) for x in out]


def op_name(event_name: str) -> str:
    """The HLO op's own name: an "XLA Ops" event is named by the op's
    whole HLO text, whose operands name other ops."""
    return event_name.split(" = ", 1)[0]


def kernel_of(op: str):
    for k, prefixes in KERNELS.items():
        if op.startswith(prefixes):
            return k
    return None


def clip(events, lo, hi):
    """Events cut to [lo, hi): a while op that began before the window
    counts only from its start."""
    out = []
    for name, s, d in events:
        a, b = max(s, lo), min(s + d, hi)
        if b > a:
            out.append((name, a, b - a))
    return out


def reduce(ev: dict, window_s: float) -> dict:
    """Busy, kernel and breakdown figures; with a ``bench.window`` host
    span in the trace, every device interval is clipped to it and its
    length is the window, else ``window_s`` is."""
    devs = ev["devices"]
    if not devs:
        raise ValueError("the trace holds no device operations")
    spans = [(s, s + d) for name, s, d in ev["host"] if name == WINDOW_SPAN]
    if spans:
        lo, hi = spans[0]
        devs = {k: clip(v, lo, hi) for k, v in devs.items()}
        window_s = (hi - lo) / 1e9
    busy_ns = []
    kernels: Dict[str, float] = collections.defaultdict(float)
    by_op: Dict[str, float] = collections.defaultdict(float)
    merged_first = None
    for name in sorted(devs):
        events = devs[name]
        merged = union((s, s + d) for _, s, d in events)
        busy_ns.append(sum(e - s for s, e in merged))
        if merged_first is None:
            merged_first = merged
        for name, _, d in events:
            op = op_name(name)
            if not op.startswith(CONTAINERS):
                by_op[op] += d / len(devs)
            k = kernel_of(op)
            if k:
                kernels[k] += d / len(devs)
    busy_s = sum(busy_ns) / len(busy_ns) / 1e9
    gaps = [(merged_first[i][1], merged_first[i + 1][0])
            for i in range(len(merged_first) - 1)]
    gaps.sort(key=lambda g: g[0] - g[1])
    labelled = []
    for s, e in gaps[:10]:
        cover: Dict[str, int] = collections.defaultdict(int)
        for hname, hs, hd in ev["host"]:
            ov = min(e, hs + hd) - max(s, hs)
            if ov > 0:
                cover[hname] += ov
        cover.pop(WINDOW_SPAN, None)
        label = (max(cover, key=cover.get) if cover
                 else "no benchmark span (host waiting for requests)")
        labelled.append([label, (e - s) / 1e9])
    top = sorted(by_op.items(), key=lambda kv: -kv[1])[:10]
    return {
        "busy_s": busy_s,
        "window_s": float(window_s),
        "kernels": {k: v / 1e9 for k, v in kernels.items()},
        "breakdown": {
            "device_ops": [[op, d / 1e9] for op, d in top],
            "idle_gaps": labelled,
        },
    }


def reduce_dir(trace_dir, window) -> dict:
    """Reduce the one ``.xplane.pb`` under ``trace_dir``; ``window`` is
    the traced window's (start, end) on the host clock."""
    from jax.profiler import ProfileData
    files = sorted(pathlib.Path(trace_dir).glob("**/*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    ev = extract(ProfileData.from_file(str(files[-1])))
    return reduce(ev, window[1] - window[0])
