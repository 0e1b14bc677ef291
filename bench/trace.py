"""Reduction of a profiler trace to device busy time, idle gaps and op times.

``extract`` reads the ``.xplane.pb`` that ``jax.profiler`` writes into a
plain structure: per device, the intervals in which an operation ran;
and the benchmark's own host spans (``bench.*`` trace annotations).
``reduce`` turns that into the numbers the per-layer metrics and the
result's ``breakdown`` read.  Both take the patterns that name device
planes and op lines, so a test can run them on a trace recorded on the
CPU.
"""
from __future__ import annotations

import bisect
import collections
import glob
import os
import re

DEVICE_PLANE = r"^/device:TPU:\d+$"
OP_LINE = r"^XLA Ops$"
SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"
# an op that runs others (a while loop's event spans its body's events):
# counted in busy time, left out of the op totals
CONTAINER = re.compile(r" (while|conditional|call)\(")
NAME_CHARS = 160   # of an op's HLO text, kept in the breakdown
# an op event named only by its region, with no HLO text: on a v5e 2x2
# trace of the mesh path, device 0 names most of its loop ops so
UNNAMED = re.compile(r"^region\.\d+$")


def xplane_file(log_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


def extract(path: str, device_plane: str = DEVICE_PLANE,
            op_line: str = OP_LINE) -> dict:
    """{"devices": {plane: [[op, start_ns, end_ns], ...]},
    "spans": [[name, start_ns, end_ns], ...]} from one xplane file."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    devices, spans = {}, []
    for plane in pd.planes:
        is_dev = re.match(device_plane, plane.name) is not None
        for line in plane.lines:
            take_ops = is_dev and re.match(op_line, line.name) is not None
            for e in line.events:
                start = float(e.start_ns)
                end = start + float(e.duration_ns)
                if take_ops and e.duration_ns > 0:
                    devices.setdefault(plane.name, []).append([e.name, start, end])
                elif e.name.startswith(SPAN_PREFIX):
                    spans.append([e.name, start, end])
    return {"devices": devices, "spans": spans}


def _union(intervals):
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def _clip(intervals, lo, hi):
    return [[max(s, lo), min(e, hi)] for s, e in intervals if e > lo and s < hi]


def _covering(host, starts, t, depth: int = 8) -> str:
    """Name of the innermost of the last ``depth`` host spans begun by
    ``t`` that still cover it; the benchmark's spans nest only shallowly."""
    i = bisect.bisect_right(starts, t)
    cover = [s for s in host[max(i - depth, 0):i] if s[2] >= t]
    return min(cover, key=lambda s: s[2] - s[1])[0] if cover else "no span"


def reduce(ev: dict, top: int = 10) -> dict:
    """Busy and idle time per device inside the ``bench.window`` span, op
    totals (loops that contain other ops left out), and idle time
    attributed to the host span that covers each gap's middle (the
    innermost ``bench.*`` span, "no span" where none)."""
    windows = [s for s in ev["spans"] if s[0] == WINDOW_SPAN]
    if windows:
        lo, hi = windows[0][1], windows[0][2]
    else:
        every = [x for ops in ev["devices"].values() for x in ops]
        lo = min((x[1] for x in every), default=0.0)
        hi = max((x[2] for x in every), default=0.0)
    window_s = (hi - lo) * 1e-9
    host = sorted((s for s in ev["spans"] if s[0] != WINDOW_SPAN),
                  key=lambda s: s[1])
    starts = [s[1] for s in host]
    busy, ops, calls = {}, collections.Counter(), collections.Counter()
    gaps_by_span = collections.Counter()
    for plane, evs in sorted(ev["devices"].items()):
        inside = _clip([[s, e] for _, s, e in evs], lo, hi)
        merged = _union(inside)
        busy[plane] = sum(e - s for s, e in merged) * 1e-9
        for name, s, e in evs:
            c = min(e, hi) - max(s, lo)
            if c > 0 and not CONTAINER.search(name):
                ops[name] += c * 1e-9
                calls[name] += 1
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        for g0, g1 in zip(edges[::2], edges[1::2]):
            if g1 <= g0:
                continue
            mid = 0.5 * (g0 + g1)
            name = _covering(host, starts, mid)
            gaps_by_span[name] += (g1 - g0) * 1e-9 / max(len(ev["devices"]), 1)
    busy_s = sum(busy.values()) / max(len(busy), 1)
    return {
        "window_s": window_s,
        "busy_s": busy_s,
        "idle_share": (1.0 - busy_s / window_s) if window_s > 0 else None,
        "ops_s": dict(ops),
        "ops_n": dict(calls),
        "device_ops": [[k[:NAME_CHARS], v / max(len(busy), 1)]
                       for k, v in ops.most_common(top)],
        "idle_gaps": [[k, v] for k, v in gaps_by_span.most_common(top)],
        "devices": len(busy),
        "window": [lo, hi],
        "events": ev["devices"],
    }


def _length(intervals) -> float:
    return sum(e - s for s, e in intervals)


def _overlap(a, b) -> float:
    """Length of the intersection of two sorted disjoint interval lists."""
    i = j = 0
    total = 0.0
    while i < len(a) and j < len(b):
        total += max(min(a[i][1], b[j][1]) - max(a[i][0], b[j][0]), 0.0)
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def exposed_seconds(red: dict, pattern: str):
    """Seconds of the traced window (averaged over devices) in which an op
    whose name matches ``pattern`` runs on a device and no other op does;
    loops that contain other ops count as neither.  A device whose trace
    leaves ops in the window unnamed cannot tell which match, and is left
    out of the average.  None where no op matches: nothing to read."""
    rx = re.compile(pattern)
    lo, hi = red["window"]
    total, read, found = 0.0, 0, False
    for evs in red["events"].values():
        inside = [x for x in evs if x[2] > lo and x[1] < hi]
        if any(UNNAMED.match(name) for name, _, _ in inside):
            continue
        mine, other = [], []
        for name, s, e in inside:
            if CONTAINER.search(name):
                continue
            (mine if rx.search(name) else other).append([s, e])
        found = found or bool(mine)
        mine = _union(_clip(mine, lo, hi))
        other = _union(_clip(other, lo, hi))
        total += (_length(mine) - _overlap(mine, other)) * 1e-9
        read += 1
    return total / read if found else None


def op_seconds(red: dict, pattern: str) -> float:
    """Summed device seconds (averaged over devices) of ops whose name
    matches ``pattern``."""
    rx = re.compile(pattern)
    total = sum(v for k, v in red["ops_s"].items() if rx.search(k))
    return total / max(red["devices"], 1)


def op_count(red: dict, pattern: str) -> float:
    """Executions (averaged over devices) of ops whose name matches."""
    rx = re.compile(pattern)
    total = sum(v for k, v in red["ops_n"].items() if rx.search(k))
    return total / max(red["devices"], 1)


def idle_percent(red):
    """Idle share of the traced window in percent; None without a trace."""
    if not red or red["idle_share"] is None or not red["devices"]:
        return None
    return 100.0 * red["idle_share"]
