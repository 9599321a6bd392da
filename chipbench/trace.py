"""Reduction of a profiler trace (``.xplane.pb``) to device busy time,
idle gaps labelled by what the host was doing, and host spans.

Device operations are the events of each ``/device:`` plane's
``XLA Ops`` line.  Host spans are the ``TraceAnnotation`` events the
harness writes around calls into the program (``SPANS``) and around the
measured window (``window``).  A moment of the window in which no device
operation runs is idle; each idle gap is named after the innermost
harness span open during most of it, or ``client`` where none is.
"""

from __future__ import annotations

import bisect
from collections import defaultdict
from pathlib import Path

#: harness span names, innermost first
SPANS = ("seam", "commit", "copy", "combine")
WINDOW = "window"
OPS_LINE = "XLA Ops"


def find_xplane(directory):
    """The one ``.xplane.pb`` file a profiler session wrote under
    ``directory``."""
    paths = sorted(Path(directory).rglob("*.xplane.pb"))
    if len(paths) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {directory}, "
                           f"found {len(paths)}")
    return paths[0]


def load(path):
    """Events of the trace at ``path`` in seconds: ``device`` is a list of
    ``(start, end, name)`` per device plane, ``spans`` maps each harness
    span name to its ``(start, end)`` list, ``window`` is the window span
    or None."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(str(path))
    device, spans, window = [], defaultdict(list), None
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            ops = [(e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9,
                    e.name)
                   for line in plane.lines if line.name == OPS_LINE
                   for e in line.events]
            device.append(ops)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name in SPANS or e.name == WINDOW:
                        iv = (e.start_ns * 1e-9,
                              (e.start_ns + e.duration_ns) * 1e-9)
                        if e.name == WINDOW:
                            window = iv
                        else:
                            spans[e.name].append(iv)
    return {"device": device, "spans": dict(spans), "window": window}


def union(intervals):
    """Sorted disjoint union of ``(start, end)`` intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def _covers(merged, starts, t):
    i = bisect.bisect_right(starts, t) - 1
    return i >= 0 and merged[i][1] > t


def label_timeline(spans, lo, hi):
    """Disjoint ``(start, end, name)`` segments of [lo, hi] during which a
    harness span is open, each named after the innermost one."""
    merged = {n: union(clip(spans.get(n, ()), lo, hi)) for n in SPANS}
    starts = {n: [s for s, _ in merged[n]] for n in SPANS}
    points = sorted({t for n in SPANS for iv in merged[n] for t in iv})
    out = []
    for a, b in zip(points, points[1:]):
        mid = (a + b) / 2
        name = next((n for n in SPANS if _covers(merged[n], starts[n], mid)),
                    None)
        if name is None:
            continue
        if out and out[-1][2] == name and out[-1][1] == a:
            out[-1] = (out[-1][0], b, name)
        else:
            out.append((a, b, name))
    return out


def name_gap(timeline, tl_starts, a, b):
    """What the host was doing during most of the gap [a, b]."""
    time = defaultdict(float)
    i = max(0, bisect.bisect_right(tl_starts, a) - 1)
    while i < len(timeline) and timeline[i][0] < b:
        s, e, name = timeline[i]
        time[name] += max(0.0, min(e, b) - max(s, a))
        i += 1
    time["client"] = (b - a) - sum(time.values())
    return max(time, key=time.get)


def reduce(events, top=10):
    """Busy and idle time of the device in the window, the ``top``
    device operations by total time, the ``top`` longest idle gaps named
    after the host's activity, and a count and total time per harness
    span.  ``busy_s`` averages the device planes; it is None where the
    trace holds no device plane (a CPU run)."""
    if events["window"] is None:
        raise ValueError("the trace has no window span")
    lo, hi = events["window"]
    spans = {n: clip(v, lo, hi) for n, v in events["spans"].items()}
    out = {"window_s": hi - lo,
           "spans": {n: {"count": len(v), "total_s": sum(e - s for s, e in v)}
                     for n, v in spans.items()},
           "busy_s": None, "device_ops": [], "idle_gaps": []}
    if not events["device"]:
        return out
    busy, per_op, gaps = [], defaultdict(float), []
    timeline = label_timeline(spans, lo, hi)
    tl_starts = [s for s, _, _ in timeline]
    for ops in events["device"]:
        ops = [(max(s, lo), min(e, hi), n) for s, e, n in ops
               if e > lo and s < hi]
        for s, e, n in ops:
            per_op[n] += e - s
        merged = union((s, e) for s, e, _ in ops)
        busy.append(sum(e - s for s, e in merged))
        edges = [lo] + [t for iv in merged for t in iv] + [hi]
        gaps += [(edges[i], edges[i + 1])
                 for i in range(0, len(edges), 2) if edges[i + 1] > edges[i]]
    gaps.sort(key=lambda g: g[0] - g[1])
    out["busy_s"] = sum(busy) / len(busy)
    out["device_ops"] = sorted(([n, t] for n, t in per_op.items()),
                               key=lambda x: -x[1])[:top]
    out["idle_gaps"] = [[name_gap(timeline, tl_starts, a, b), b - a]
                        for a, b in gaps[:top]]
    return out
