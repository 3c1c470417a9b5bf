"""Reduction of a profiler trace to the benchmark's device numbers.

The profiler writes an ``.xplane.pb``; :func:`read_events` flattens it
into :class:`Event` rows (plane, line, name, start and duration in
nanoseconds) -- the device's operations and the benchmark's own host
spans -- and :func:`summarize`
reduces those rows, inside the benchmark's ``bench.window`` host span,
to:

* ``busy_s`` -- the union of the intervals in which an operation ran on
  a device, averaged over the devices that ran one; ``window_s`` -- the
  length of the window span;
* ``kernel_s`` -- summed device time of the operations whose name
  holds a kernel's name;
* ``device_ops`` -- the device operations that took most time, by name,
  each counted by its self time (less the operations nested in it);
* ``idle_gaps`` -- the device's idle time inside the window, named by
  the innermost ``bench.*`` host span open at each gap's midpoint.

Host spans and device operations share the profiler's clock.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import gzip
import json
import os
from collections import defaultdict

#: device planes are named ``/device:<KIND>:<id>``; their operations sit
#: on this line
DEVICE_PLANE_PREFIX = "/device:"
DEVICE_OPS_LINE = "XLA Ops"
#: host spans the benchmark opens (see ``loadgen.Spans``)
SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"
TOP = 10


@dataclasses.dataclass(frozen=True)
class Event:
    plane: str
    line: str
    name: str
    start: float        # ns
    dur: float          # ns

    @property
    def end(self) -> float:
        return self.start + self.dur


def short_name(name: str) -> str:
    """``%fusion.103 = f32[...] fusion(...)`` -> ``fusion.103``: a device
    operation's trace name is its whole HLO instruction."""
    return name.split(" = ", 1)[0].lstrip("%")


def read_events(trace_dir: str) -> list:
    """Every event of the newest ``.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(max(paths, key=os.path.getmtime))
    out = []
    for plane in data.planes:
        device = plane.name.startswith(DEVICE_PLANE_PREFIX)
        for line in plane.lines:
            if device and line.name != DEVICE_OPS_LINE:
                continue
            for ev in line.events:
                if device:
                    name = short_name(ev.name)
                elif ev.name.startswith(SPAN_PREFIX):
                    name = ev.name
                else:
                    continue            # host events other than our spans
                out.append(Event(plane.name, line.name, name,
                                 float(ev.start_ns), float(ev.duration_ns)))
    return out


def save_events(events, path: str) -> None:
    """Write events as gzipped JSON rows (the tests' fixture format)."""
    with gzip.open(path, "wt") as f:
        json.dump([dataclasses.astuple(e) for e in events], f)


def load_events(path: str) -> list:
    with gzip.open(path, "rt") as f:
        return [Event(*row) for row in json.load(f)]


def union(intervals) -> list:
    """Sorted, merged ``[(start, end)]`` of possibly overlapping ones."""
    merged: list = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1][1] = e
        else:
            merged.append([s, e])
    return [tuple(m) for m in merged]


def clip(intervals, lo: float, hi: float) -> list:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def gaps(merged, lo: float, hi: float) -> list:
    """The complement of merged intervals inside ``[lo, hi]``."""
    out, t = [], lo
    for s, e in merged:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def window_of(events) -> tuple:
    spans = [e for e in events if e.name == WINDOW_SPAN]
    if not spans:
        raise ValueError(f"the trace holds no {WINDOW_SPAN} span")
    w = max(spans, key=lambda e: e.dur)
    return w.start, w.end


def device_ops(events) -> dict:
    """``{device plane: [ops]}`` of the planes that ran an operation."""
    by = defaultdict(list)
    for e in events:
        if e.plane.startswith(DEVICE_PLANE_PREFIX) and \
                e.line == DEVICE_OPS_LINE:
            by[e.plane].append(e)
    return dict(by)


def self_times(ops) -> list:
    """``[(op, self ns)]``: each operation's duration less that of the
    operations nested in it on the same line (a ``while`` holds its
    body's operations)."""
    out, stack = [], []                 # stack: [op, children's ns]
    for o in sorted(ops, key=lambda o: (o.start, -o.dur)):
        while stack and o.start >= stack[-1][0].end:
            done = stack.pop()
            out.append((done[0], done[0].dur - done[1]))
        if stack:
            stack[-1][1] += o.dur
        stack.append([o, 0.0])
    out += [(o, o.dur - kids) for o, kids in reversed(stack)]
    return out


def span_at(spans, starts, t: float) -> str:
    """Name of the span open at ``t``, or ``none``.  ``spans`` are sorted
    by start and do not overlap (the benchmark opens one at a time
    inside the window); ``starts`` are their starts."""
    i = bisect.bisect_right(starts, t) - 1
    if i >= 0 and t < spans[i].end:
        return spans[i].name[len(SPAN_PREFIX):]
    return "none"


def summarize(events, kernels=()) -> dict:
    """Device numbers of the window (see the module docstring)."""
    lo, hi = window_of(events)
    planes = device_ops(events)
    if not planes:
        raise ValueError("the trace holds no device operation")
    busy, idle = [], defaultdict(float)
    by_op, kernel_ns = defaultdict(float), defaultdict(float)
    spans = sorted((e for e in events if e.name.startswith(SPAN_PREFIX)
                    and e.name != WINDOW_SPAN), key=lambda e: e.start)
    starts = [e.start for e in spans]
    for ops in planes.values():
        inside = [dataclasses.replace(o, start=max(o.start, lo),
                                      dur=min(o.end, hi) - max(o.start, lo))
                  for o in ops if o.end > lo and o.start < hi]
        merged = union((o.start, o.end) for o in inside)
        busy.append(sum(e - s for s, e in merged))
        for s, e in gaps(merged, lo, hi):
            idle[span_at(spans, starts, (s + e) / 2)] += (e - s) / len(planes)
        for o, own in self_times(inside):
            by_op[o.name] += own / len(planes)
            for k in kernels:
                if k in o.name:
                    kernel_ns[k] += own / len(planes)
    top = lambda d: [[k, v / 1e9] for k, v in            # noqa: E731
                     sorted(d.items(), key=lambda kv: -kv[1])[:TOP]]
    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": sum(busy) / len(busy) / 1e9,
        "devices": len(planes),
        "kernel_s": {k: kernel_ns[k] / 1e9 for k in kernels},
        "device_ops": top(by_op),
        "idle_gaps": top(idle),
    }


def idle_pct(summary) -> float:
    """Share of the window in which no operation ran on the device, in
    percent; ``None`` without a summary."""
    if summary is None or summary["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - summary["busy_s"] / summary["window_s"])
