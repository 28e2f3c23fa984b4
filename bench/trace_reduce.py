"""From a profiler trace to busy time, kernel time, scopes and a breakdown.

Two steps, kept apart so that the second can be checked on a small
recorded trace without a chip:

1. ``read_xspace(data)`` reads a serialized ``XSpace`` (the
   ``.xplane.pb`` file) into plain lists: the device's operation events
   (the "XLA Ops" line of each ``/device:TPU:<n>`` plane), the host's
   named spans this benchmark writes (``bench.*``, ``bench.window``
   among them: the measured window on the trace's own clock), the
   program's own host spans (``serve.*``) with the thread line each ran
   on, and each device operation's ``op_name`` path, the ``tf_op`` stat
   of its event metadata, which ``jax.profiler.ProfileData`` does not
   show (``op_paths`` reads it from the wire format).
2. ``reduce(ev, window_s)`` computes the union of the device intervals
   (busy seconds, averaged over the chips); the summed device time of
   every custom call by the stem of its HLO name (``kernels``); the
   device time of the operations under each component of their
   ``op_name`` paths (``scope_busy_s``: a ``jax.named_scope`` is such a
   component) and the share of operation time that has a path; the ten
   operations that took most time (control-flow ops such as ``while``,
   whose events span their bodies, left out); the ten longest idle gaps
   labelled by the benchmark's host span that covered most of each; and
   the window's idle time by the program span that covered it
   (``idle_by_span``).
"""

from __future__ import annotations

import collections
import pathlib
import re
from typing import Dict, List

# control-flow ops whose events span the ops they run
CONTAINERS = ("%while", "%cond", "%conditional", "%call")
CUSTOM_CALL = " custom-call("    # in an event's HLO text: a kernel
OPS_LINE = "XLA Ops"
DEVICE_PLANE = "/device:TPU:"
HOST_SPAN_PREFIX = "bench."
PROGRAM_SPAN_PREFIX = "serve."
WINDOW_SPAN = "bench.window"    # the measured window, on the trace's clock
PATH_STAT = "tf_op"             # event-metadata stat holding op_name
NO_SPAN = "no program span"
MAX_ENTRIES = 10                # per breakdown list


def extract(profile) -> dict:
    devices: Dict[str, List[tuple]] = {}
    host: List[tuple] = []
    program: List[tuple] = []
    for pi, plane in enumerate(profile.planes):
        if plane.name.startswith(DEVICE_PLANE):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    devices[plane.name] = [
                        (e.name, e.start_ns, e.duration_ns)
                        for e in line.events]
        elif plane.name.startswith("/host:"):
            for li, line in enumerate(plane.lines):
                for e in line.events:
                    if e.name.startswith(HOST_SPAN_PREFIX):
                        host.append((e.name, e.start_ns, e.duration_ns))
                    elif e.name.startswith(PROGRAM_SPAN_PREFIX):
                        program.append((e.name, e.start_ns, e.duration_ns,
                                        f"{pi}:{li}"))
    return {"devices": devices, "host": host, "program": program}


# ---------------- op_name paths, from the wire format --------------------

def _varint(buf, pos: int) -> tuple:
    out = shift = 0
    while True:
        b = buf[pos]
        pos += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, pos
        shift += 7


def _fields(buf, pos: int, end: int):
    """(field number, value) of the protobuf message in ``buf[pos:end]``;
    a length-delimited value is given as its (start, end)."""
    while pos < end:
        key, pos = _varint(buf, pos)
        num, wire = key >> 3, key & 7
        if wire == 0:
            val, pos = _varint(buf, pos)
        elif wire == 2:
            n, pos = _varint(buf, pos)
            val, pos = (pos, pos + n), pos + n
        elif wire in (1, 5):
            n = 8 if wire == 1 else 4
            val, pos = None, pos + n
        else:
            raise ValueError(f"protobuf wire type {wire} at {pos}")
        yield num, val


def _text(buf, span) -> str:
    return bytes(buf[span[0]:span[1]]).decode("utf-8", "replace")


def _map_value(buf, span):
    """The value (field 2) of a protobuf map entry."""
    for num, val in _fields(buf, *span):
        if num == 2:
            return val
    return None


def op_paths(data: bytes) -> Dict[str, str]:
    """Event name -> ``op_name`` path of every device operation whose
    event metadata carries the ``tf_op`` stat (``XSpace`` field numbers:
    planes 1; ``XPlane`` name 2, event_metadata 4, stat_metadata 5;
    ``XEventMetadata`` name 2, stats 5; ``XStat`` metadata_id 1,
    str_value 5, ref_value 7; ``XStatMetadata`` id 1, name 2). The
    event lines, most of the file, are skipped by their length."""
    buf = memoryview(data)
    out: Dict[str, str] = {}
    for num, plane in _fields(buf, 0, len(buf)):
        if num != 1:
            continue
        name, metas, stat_names = "", [], {}
        for n, val in _fields(buf, *plane):
            if n == 2:
                name = _text(buf, val)
            elif n == 4:
                metas.append(val)
            elif n == 5:
                sid, sname = None, ""
                for m, v in _fields(buf, *_map_value(buf, val)):
                    if m == 1:
                        sid = v
                    elif m == 2:
                        sname = _text(buf, v)
                stat_names[sid] = sname
        if not name.startswith(DEVICE_PLANE):
            continue
        path_ids = {k for k, v in stat_names.items() if v == PATH_STAT}
        for entry in metas:
            ename, path = None, None
            for m, v in _fields(buf, *_map_value(buf, entry)):
                if m == 2:
                    ename = _text(buf, v)
                elif m == 5:
                    sid, sval = None, None
                    for f, x in _fields(buf, *v):
                        if f == 1:
                            sid = x
                        elif f == 5:
                            sval = _text(buf, x)
                        elif f == 7:
                            sval = stat_names.get(x)
                    if sid in path_ids and sval:
                        path = sval
            if ename is not None and path:
                out[ename] = path
    return out


def read_xspace(data: bytes) -> dict:
    """``extract`` of a serialized ``XSpace``, with ``op_paths``."""
    from jax.profiler import ProfileData
    ev = extract(ProfileData.from_serialized_xspace(data))
    ev["op_paths"] = op_paths(data)
    return ev


# ---------------- the reduction ----------------------------------------

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


def kernel_of(event_name: str):
    """The stem of a custom call's HLO name ("%paged_attention.9 = ...
    custom-call(...)" -> "paged_attention"), None for any other op."""
    if CUSTOM_CALL not in event_name:
        return None
    return re.sub(r"(\.\d+)+$", "", op_name(event_name).lstrip("%"))


def clip(events, lo, hi):
    """Events cut to [lo, hi): a while op that began before the window
    counts only from its start."""
    out = []
    for name, s, d, *rest in events:
        a, b = max(s, lo), min(s + d, hi)
        if b > a:
            out.append((name, a, b - a, *rest))
    return out


def scope_names(path: str) -> set:
    """The components of an ``op_name`` path, the last one without the
    ``:<type>`` the trace appends to it ("jit(step)/serve.decode/
    dot_general:" -> {"jit(step)", "serve.decode", "dot_general"})."""
    comps = path.split("/")
    comps[-1] = comps[-1].split(":", 1)[0]
    return {c for c in comps if c}


def idle_intervals(merged, lo, hi) -> List[tuple]:
    """The parts of [lo, hi) that no merged busy interval covers."""
    out, t = [], lo
    for s, e in merged:
        if s > t:
            out.append((t, min(s, hi)))
        t = max(t, e)
    if t < hi:
        out.append((t, hi))
    return [(s, e) for s, e in out if e > s]


def idle_by_span(idle_per_chip, spans) -> Dict[str, float]:
    """Idle ns of each chip, averaged over the chips, by the program span
    covering it: of the threads with a covering span, the one whose
    outermost covering span began first (what the others wait behind,
    as a submission waits on a round's lock), and there its innermost
    covering span, the one that began last (the shortest, where two
    began together). Idle no span covers goes to ``NO_SPAN``."""
    n_chips = len(idle_per_chip)
    points = []                  # (t, kind, payload): kind 0 idle, 1 span
    for chip in idle_per_chip:
        for s, e in chip:
            points += [(s, 0, 1), (e, 0, -1)]
    for i, (name, s, d, line) in enumerate(spans):
        points += [(s, 1, i), (s + d, 1, ~i)]
    points.sort(key=lambda p: p[0])
    idle = 0
    active: Dict[str, dict] = collections.defaultdict(dict)
    out: Dict[str, float] = collections.defaultdict(float)
    for k, (t, kind, x) in enumerate(points):
        if kind == 0:
            idle += x
        elif x >= 0:
            active[spans[x][3]][x] = spans[x][1]
        else:
            active[spans[~x][3]].pop(~x, None)
        dt = points[k + 1][0] - t if k + 1 < len(points) else 0
        if idle > 0 and dt > 0:
            lines = [a for a in active.values() if a]
            label = NO_SPAN
            if lines:
                a = min(lines, key=lambda a: min(a.values()))
                label = spans[max(a, key=lambda i: (a[i], -spans[i][2]))][0]
            out[label] += idle * dt / n_chips
    return dict(out)


def top_entries(d: Dict[str, float]) -> List[list]:
    """``[[name, seconds], ...]`` of a ns table, largest first, at most
    ``MAX_ENTRIES`` long: what does not fit is summed as "other"."""
    items = sorted(d.items(), key=lambda kv: -kv[1])
    if len(items) > MAX_ENTRIES:
        rest = sum(v for _, v in items[MAX_ENTRIES - 1:])
        items = items[:MAX_ENTRIES - 1] + [("other", rest)]
    return [[k, v / 1e9] for k, v in items]


def reduce(ev: dict, window_s: float) -> dict:
    """Busy, kernel, scope and breakdown figures; with a
    ``bench.window`` host span in the trace, every device interval and
    program span is clipped to it and its length is the window, else
    ``window_s`` from the first device operation is."""
    devs = ev["devices"]
    if not devs:
        raise ValueError("the trace holds no device operations")
    spans = [(s, s + d) for name, s, d in ev["host"] if name == WINDOW_SPAN]
    if spans:
        lo, hi = spans[0]
        devs = {k: clip(v, lo, hi) for k, v in devs.items()}
        window_s = (hi - lo) / 1e9
    else:
        lo = min(s for v in devs.values() for _, s, _ in v)
        hi = lo + int(window_s * 1e9)
    program = clip(ev.get("program", []), lo, hi)
    paths = ev.get("op_paths") or {}
    n_chips = len(devs)
    busy_ns, idle_per_chip = [], []
    kernels: Dict[str, float] = collections.defaultdict(float)
    by_op: Dict[str, float] = collections.defaultdict(float)
    scoped_ns: Dict[str, int] = collections.defaultdict(int)
    ops: Dict[str, tuple] = {}   # event name -> (op, container, kernel)
    op_ns = 0
    merged_first = None
    for name in sorted(devs):
        events = devs[name]
        merged = union((s, s + d) for _, s, d in events)
        busy_ns.append(sum(e - s for s, e in merged))
        idle_per_chip.append(idle_intervals(merged, lo, hi))
        if merged_first is None:
            merged_first = merged
        for name, _, d in events:
            if name not in ops:
                op = op_name(name)
                ops[name] = (op, op.startswith(CONTAINERS), kernel_of(name))
            op, container, k = ops[name]
            if container:
                continue
            by_op[op] += d / n_chips
            if k:
                kernels[k] += d / n_chips
            op_ns += d
            if name in paths:
                scoped_ns[name] += d
    scopes: Dict[str, float] = collections.defaultdict(float)
    for name, ns in scoped_ns.items():
        for c in scope_names(paths[name]):
            scopes[c] += ns / n_chips
    mapped_ns = sum(scoped_ns.values())
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
        "scope_busy_s": {k: v / 1e9 for k, v in scopes.items()},
        "scope_mapped_share": (100.0 * mapped_ns / op_ns if op_ns
                               else None),
        "breakdown": {
            "device_ops": [[op, d / 1e9] for op, d in top],
            "idle_gaps": labelled,
            "idle_by_span": top_entries(
                idle_by_span(idle_per_chip, program)),
        },
    }


def reduce_dir(trace_dir, window) -> dict:
    """Reduce the one ``.xplane.pb`` under ``trace_dir``; ``window`` is
    the traced window's (start, end) on the host clock."""
    files = sorted(pathlib.Path(trace_dir).glob("**/*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    ev = read_xspace(files[-1].read_bytes())
    return reduce(ev, window[1] - window[0])
