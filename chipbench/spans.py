#!/usr/bin/env python3
"""The program's own spans and counters (``repro.core.tracing``), read
beside the benchmark's: a traced run of one cell that also reports what
the host does inside each combining round.

    python3 chipbench/spans.py --workload counter_pb.closed --seed 7 \
        --seconds 30 --trace 1 [--keep-trace DIR]

It runs ``run.run_cell`` as ``run.py`` does, with three additions:

* in a traced run the program's tracing is on for the window alone,
  switched where ``run.counters`` reads the counters at the window's
  edges, and those counters gain the program's ``waiter_polls``,
  ``queue_ns`` and ``queued_ops`` where it has them;
* the trace's program spans, device planes and XLA modules are read and
  reduced (``load_program``, ``reduce_program``) next to ``trace.py``'s
  reduction;
* the metrics of ``METRICS`` are reported besides the cell's own, with
  ``ops_per_s`` and ``p99_op_ms`` in both kinds of run, so that a traced
  run can be held against an untraced one.

Before the result line it prints a ``"phase": "spans"`` line: each
``/device:`` plane with its op count inside and outside the window,
device time per XLA module, the window, the interpreter's switch
interval, each program span's count and total, the share of seam calls
that contain the end of a device op, and the idle gaps named after the
innermost program or harness span.  A program without
``repro.core.tracing`` runs too; its new metrics read None and are left
out.  ``run.py`` and ``trace.py`` are unchanged: the benchmark's own runs
see none of this.
"""

from __future__ import annotations

import bisect
import json
import sys
from collections import defaultdict
from pathlib import Path

HERE = Path(__file__).resolve().parent
if str(HERE.parent) not in sys.path:
    sys.path.insert(0, str(HERE.parent))

from chipbench import run, trace  # noqa: E402

#: the program's spans, innermost first; all are inner to trace.SPANS
PROGRAM_SPANS = ("seam.fetch", "seam.dispatch", "seam.gather",
                 "seam.scatter", "combine.host_apply", "combine.scan")
COUNTERS = ("waiter_polls", "queue_ns", "queued_ops")
MODULES_LINE = "XLA Modules"
#: the metrics this script adds, and the cell's own it reports in both
#: kinds of run
METRICS = [
    {"name": "queue_ms_per_op", "unit": "ms"},
    {"name": "waiter_polls_per_op", "unit": "polls/op"},
    {"name": "scan_ms_per_round", "unit": "ms/round"},
    {"name": "host_apply_ms_per_round", "unit": "ms/round"},
    {"name": "seam_copy_ms_per_call", "unit": "ms/call"},
    {"name": "dispatch_ms_per_call", "unit": "ms/call"},
    {"name": "fetch_ms_per_call", "unit": "ms/call"},
    {"name": "seam_wake_ms_per_call", "unit": "ms/call"},
]
BOTH = [{"name": "ops_per_s", "unit": "ops/s"},
        {"name": "p99_op_ms", "unit": "ms"}]


# ------------------------------------------------------------------ #
# reading a trace                                                    #
# ------------------------------------------------------------------ #
def load_program(path):
    """What ``trace.load`` leaves out of the trace at ``path``, in
    seconds: ``program`` maps each program span to its ``(start, end,
    thread)`` list (a thread is a host line); ``planes`` lists each
    ``/device:`` plane as ``{"name", "lines", "ops", "modules"}``, its
    ops and XLA modules as ``(start, end, name)``."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(str(path))
    program, planes = defaultdict(list), []
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            lines = {line.name: [(e.start_ns * 1e-9,
                                  (e.start_ns + e.duration_ns) * 1e-9,
                                  e.name) for e in line.events]
                     for line in plane.lines}
            planes.append({"name": plane.name, "lines": sorted(lines),
                           "ops": lines.get(trace.OPS_LINE, []),
                           "modules": lines.get(MODULES_LINE, [])})
        elif plane.name.startswith("/host:"):
            for i, line in enumerate(plane.lines):
                for e in line.events:
                    name = e.name.split("#")[0]
                    if name in PROGRAM_SPANS:
                        program[name].append(
                            (e.start_ns * 1e-9,
                             (e.start_ns + e.duration_ns) * 1e-9,
                             (plane.name, i)))
    return {"program": dict(program), "planes": planes}


def _clip3(events, lo, hi):
    return [(max(s, lo), min(e, hi), n) for s, e, n in events
            if e > lo and s < hi]


def label_timeline(spans, names, lo, hi):
    """``trace.label_timeline`` over ``names``, innermost first."""
    merged = {n: trace.union(trace.clip(spans.get(n, ()), lo, hi))
              for n in names}
    starts = {n: [s for s, _ in merged[n]] for n in names}
    points = sorted({t for n in names for iv in merged[n] for t in iv})
    out = []
    for a, b in zip(points, points[1:]):
        mid = (a + b) / 2
        name = next((n for n in names
                     if trace._covers(merged[n], starts[n], mid)), None)
        if name is None:
            continue
        if out and out[-1][2] == name and out[-1][1] == a:
            out[-1] = (out[-1][0], b, name)
        else:
            out.append((a, b, name))
    return out


def wakes(program, planes, lo, hi):
    """For each ``seam.fetch`` in [lo, hi], the seam call from its
    thread's last ``seam.dispatch`` start to its own end: the time from
    the last device op ending inside that call to the call's end.
    Returns ``(wakes, calls)``; a call in which no op of a plane that
    holds ops ends has no wake."""
    ends = sorted(e for p in planes for _s, e, _n in p["ops"])
    dispatch = defaultdict(list)
    for s, _e, thread in program.get("seam.dispatch", ()):
        dispatch[thread].append(s)
    for starts in dispatch.values():
        starts.sort()
    out, calls = [], 0
    for fs, fe, thread in program.get("seam.fetch", ()):
        if not lo <= fs < hi:
            continue
        starts = dispatch.get(thread, [])
        i = bisect.bisect_right(starts, fs) - 1
        if i < 0:
            continue
        calls += 1
        j = bisect.bisect_right(ends, fe) - 1
        if j >= 0 and ends[j] >= starts[i]:
            out.append(fe - ends[j])
    return out, calls


def reduce_program(events, top=10):
    """The program's spans in the window (count and total), the wake of
    every seam call, and the device seen through the planes that hold
    ops: busy time, op counts per plane, time per XLA module, and the
    ``top`` longest idle gaps named after the innermost program or
    harness span open during most of each (``client`` where none is)."""
    lo, hi = events["window"]
    program = {n: [(s, e) for s, e, _t in v]
               for n, v in events["program"].items()}
    spans = {n: trace.clip(v, lo, hi) for n, v in program.items()}
    planes = events["planes"]
    busy = [p for p in planes if _clip3(p["ops"], lo, hi)]
    woke, calls = wakes(events["program"], busy, lo, hi)
    module_s = defaultdict(float)
    for p in planes:
        for s, e, name in _clip3(p["modules"], lo, hi):
            module_s[name.split("(")[0]] += e - s
    out = {
        "window_s": hi - lo,
        "spans": {n: {"count": len(v), "total_s": sum(e - s for s, e in v)}
                  for n, v in spans.items()},
        "wake_s": woke, "seam_calls": calls,
        "planes": [{"name": p["name"], "lines": p["lines"],
                    "ops_in_window": sum(1 for s, e, _ in p["ops"]
                                         if e > lo and s < hi),
                    "ops_outside": sum(1 for s, e, _ in p["ops"]
                                       if not (e > lo and s < hi))}
                   for p in planes],
        "module_ms": {n: t * 1e3 for n, t in sorted(module_s.items())},
        "busy_s": None, "idle_gaps": []}
    if not busy:
        return out
    names = PROGRAM_SPANS + trace.SPANS
    timeline = label_timeline(
        dict(events["spans"], **program), names, lo, hi)
    tl_starts = [s for s, _, _ in timeline]
    merged = [trace.union((s, e) for s, e, _ in _clip3(p["ops"], lo, hi))
              for p in busy]
    out["busy_s"] = sum(e - s for m in merged for s, e in m) / len(merged)
    gaps = []
    for m in merged:
        edges = [lo] + [t for iv in m for t in iv] + [hi]
        gaps += [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                 if edges[i + 1] > edges[i]]
    gaps.sort(key=lambda g: g[0] - g[1])
    out["idle_gaps"] = [[trace.name_gap(timeline, tl_starts, a, b), b - a]
                        for a, b in gaps[:top]]
    return out


# ------------------------------------------------------------------ #
# one run                                                            #
# ------------------------------------------------------------------ #
def program_tracing():
    """The program's ``repro.core.tracing``, or None where it has none."""
    try:
        from repro.core import tracing
    except ImportError:
        return None
    return tracing


class Hooks:
    """What this script adds to ``run.run_cell``, installed on the
    modules it calls: ``run.counters``, ``trace.load`` and
    ``trace.reduce``.  ``program`` holds the last reduction."""

    def __init__(self, program_tracing):
        self.program_tracing = program_tracing
        self.program = None
        self._counters, self._load, self._reduce = (
            run.counters, trace.load, trace.reduce)
        self._reads = 0

    def install(self):
        run.counters, trace.load, trace.reduce = (
            self.counters, self.load, self.reduce)

    def remove(self):
        run.counters, trace.load, trace.reduce = (
            self._counters, self._load, self._reduce)
        tracing = program_tracing()
        if tracing is not None:
            tracing.disable()

    def counters(self, rt, obj, vr, platform, seam):
        """The benchmark's counters and the program's, read at the
        window's start and end; the program's tracing is switched on
        after the first read and off after the second."""
        out = self._counters(rt, obj, vr, platform, seam)
        stats = obj.adapter.degree_stats(obj.core)
        out.update({k: stats[k] for k in COUNTERS if k in stats})
        self._reads += 1
        tracing = program_tracing() if self.program_tracing else None
        if tracing is not None:
            (tracing.enable if self._reads == 1 else tracing.disable)()
        return out

    def load(self, path):
        events = self._load(path)
        events.update(load_program(path))
        return events

    def reduce(self, events, top=10):
        out = self._reduce(events, top)
        self.program = reduce_program(events, top)
        out["program"] = self.program
        return out


def spans_line(result, program, cell):
    """The ``"phase": "spans"`` log line of a traced run."""
    calls = program["seam_calls"]
    return {"phase": "spans", "workload": cell["name"],
            "window_s": program["window_s"],
            "switch_interval_s": sys.getswitchinterval(),
            "planes": program["planes"], "module_ms": program["module_ms"],
            "busy_s_op_planes": program["busy_s"],
            "program_spans": program["spans"],
            "seam_calls": calls,
            "seam_calls_with_device_end": (len(program["wake_s"]) / calls
                                           if calls else None),
            "idle_gaps": program["idle_gaps"]}


def main(argv=None):
    opts = run.parse_args(argv)
    cell = run.load_cell(opts.workload)
    cell["per_layer"] = cell["per_layer"] + METRICS + BOTH[:1]
    cell["end_to_end"] = cell["end_to_end"] + BOTH[1:]
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chipbench: needs a TPU, JAX's first device is "
              f"{devices[0].platform!r}", file=sys.stderr)
        return 2
    run.use_compile_cache(jax)
    hooks = Hooks(bool(opts.trace))
    hooks.install()
    try:
        result = run.run_cell(cell, opts.seed, opts.seconds,
                              bool(opts.trace), devices,
                              keep_trace=opts.keep_trace)
    except run.RunError as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 3
    finally:
        hooks.remove()
    if hooks.program is not None:
        print(json.dumps(spans_line(result, hooks.program, cell)),
              flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
