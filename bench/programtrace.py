"""The program's own spans and device scopes in a traced run.

Between ``start_spans()`` and ``stop_spans()`` the program
(``repro.core.metrics``) keeps each host span it opens in memory (name,
start and end on ``perf_counter_ns``, the span open around it, and the
batch call's or request's id) and writes it into the profiler's trace as
``repro.<name>``, on the clock of the device operations.  The XLA SpMVs
and the stream VM's modules carry ``jax.named_scope`` names, which reach
each device operation's ``op_name`` metadata.

The harness loads a traced run's per-layer readers before its window and
calls them after it.  A reader of program spans calls :func:`arm` as it
is loaded, which switches the recorder on, and reads
:func:`view_of` (the run's records, and its trace read again with the
program's spans and scopes).  A program without the recorder has nothing
to switch on, and its trace no scope: the readers then return nothing.

:func:`summarize` reduces the events of :func:`read_events` inside the
``bench.window`` span as ``bench/tracefile.py`` does, and adds:

* ``scope_s`` -- device self time by program scope (:data:`SCOPES`);
* ``device_ops`` -- the operations that took most time, an operation
  with a program scope named ``<op>@<scope>``;
* ``idle_gaps`` -- the device's idle time named by the innermost span,
  the benchmark's or the program's, open at each gap's midpoint, without
  its prefix (``call``, ``batch.pack``, ``engine.admit.warm``).

``window_s``, ``busy_s`` and ``kernel_s`` are ``bench/tracefile.py``'s.
"""
from __future__ import annotations

import collections
import dataclasses
import gzip
import importlib
import json
import os
import pathlib
import statistics
import sys
import types
from collections import defaultdict

from bench import tracefile as T

#: where ``bench/run.py`` writes a traced run's profile
TRACES = pathlib.Path(__file__).resolve().parents[1] / ".bench" / "trace"
PROGRAM_PREFIX = "repro."
#: the program's device scopes (``jax.named_scope``); none may hold a
#: kernel's name, which ``bench/tracefile.py`` matches by substring
SCOPES = ("m1_xla_sell", "m1_xla_rowell", "vm_dot", "vm_axpy", "vm_div",
          "vm_ctrl")
#: the stat of a device operation's metadata that holds its ``op_name``
OP_NAME_STAT = "tf_op"


@dataclasses.dataclass(frozen=True)
class Event(T.Event):
    """A ``bench/tracefile.py`` event with the program scope of a device
    operation (``""`` for none, and for host spans)."""
    scope: str = ""


def _program():
    try:
        return importlib.import_module("repro.core.metrics")
    except ImportError:
        return None


def arm() -> None:
    """Switch the program's span recorder on, where it has one."""
    start = getattr(_program(), "start_spans", None)
    if start is not None:
        start()


def scope_of(op_name: str) -> str:
    """The innermost program scope in an ``op_name`` path
    (``jit(step)/while/body/m1_xla_sell/gather`` -> ``m1_xla_sell``)."""
    for part in reversed(op_name.replace(":", "/").split("/")):
        if part in SCOPES:
            return part
    return ""


def _varint(buf, i: int) -> tuple:
    value = shift = 0
    while True:
        byte = buf[i]
        i += 1
        value |= (byte & 0x7F) << shift
        if byte < 0x80:
            return value, i
        shift += 7


def _fields(buf):
    """``(field number, value)`` of a protobuf message: an int for a
    varint, a slice of ``buf`` for the other wire types."""
    i = 0
    while i < len(buf):
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            value, i = buf[i:i + size], i + size
        else:
            raise ValueError(f"protobuf wire type {wire} at byte {i}")
        yield key >> 3, value


def _map(entry) -> tuple:
    """Key and value of a protobuf map entry."""
    kv = dict(_fields(entry))
    return kv.get(1, 0), kv.get(2, b"")


def op_names(path) -> dict:
    """``{device plane: {operation's trace name: op_name}}`` from an
    ``.xplane.pb``.  The profiler keeps the ``op_name`` as a stat of the
    operation's event metadata, which ``jax.profiler.ProfileData`` does
    not show, so the ``XSpace`` message is read here: planes (field 1)
    have a name (2), event metadata (4: name 2, stats 5) and stat
    metadata (5: name 2); a stat (metadata id 1) holds a string (5) or
    a reference to a stat metadata's name (7)."""
    buf = memoryview(pathlib.Path(path).read_bytes())
    out = {}
    for num, plane in _fields(buf):
        if num != 1:
            continue
        name, events, stat_names = "", [], {}
        for field, value in _fields(plane):
            if field == 2:
                name = bytes(value).decode()
            elif field == 4:
                events.append(_map(value)[1])
            elif field == 5:
                sid, meta = _map(value)
                stat_names[sid] = bytes(dict(_fields(meta)).get(2, b""))\
                    .decode()
        if not name.startswith(T.DEVICE_PLANE_PREFIX):
            continue
        ops = out.setdefault(name, {})
        for meta in events:
            op, op_name = "", ""
            for field, value in _fields(meta):
                if field == 2:
                    op = bytes(value).decode()
                elif field == 5:
                    stat = dict(_fields(value))
                    if stat_names.get(stat.get(1)) != OP_NAME_STAT:
                        continue
                    if 5 in stat:
                        op_name = bytes(stat[5]).decode()
                    elif 7 in stat:
                        op_name = stat_names.get(stat[7], "")
            if op_name:
                ops[op] = op_name
    return out


def read_events(trace_dir) -> list:
    """Every event ``bench/tracefile.py`` keeps, each device operation
    with its scope, and the program's ``repro.*`` host spans."""
    from jax.profiler import ProfileData
    paths = list(pathlib.Path(trace_dir).glob("**/*.xplane.pb"))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    path = str(max(paths, key=os.path.getmtime))
    data, names = ProfileData.from_file(path), op_names(path)
    out = []
    for plane in data.planes:
        device = plane.name.startswith(T.DEVICE_PLANE_PREFIX)
        op_name = names.get(plane.name, {})
        for line in plane.lines:
            if device and line.name != T.DEVICE_OPS_LINE:
                continue
            for ev in line.events:
                scope = ""
                if device:
                    name = T.short_name(ev.name)
                    scope = scope_of(op_name.get(ev.name, ""))
                elif ev.name.startswith((T.SPAN_PREFIX, PROGRAM_PREFIX)):
                    name = ev.name
                else:
                    continue
                out.append(Event(plane.name, line.name, name,
                                 float(ev.start_ns), float(ev.duration_ns),
                                 scope))
    return out


def load_events(path: str) -> list:
    """Events saved by ``bench.tracefile.save_events``, with or without
    scopes."""
    with gzip.open(path, "rt") as f:
        return [Event(*row) for row in json.load(f)]


def innermost(spans, points) -> list:
    """For each of the sorted ``points``, the name of the innermost of
    ``spans`` (properly nested, as on one thread) open there, or
    ``None``."""
    spans = sorted(spans, key=lambda e: (e.start, -e.dur))
    out, stack, i = [], [], 0
    for t in points:
        while i < len(spans) and spans[i].start <= t:
            while stack and stack[-1].end <= spans[i].start:
                stack.pop()
            stack.append(spans[i])
            i += 1
        while stack and stack[-1].end <= t:
            stack.pop()
        out.append(stack[-1].name if stack else None)
    return out


def _bare(name) -> str:
    if name is None:
        return "none"
    for prefix in (T.SPAN_PREFIX, PROGRAM_PREFIX):
        if name.startswith(prefix):
            return name[len(prefix):]
    return name


def summarize(events, kernels=()) -> dict:
    """``bench/tracefile.py``'s summary with the program's spans and
    scopes (see the module docstring)."""
    out = T.summarize(events, kernels=kernels)
    lo, hi = T.window_of(events)
    planes = T.device_ops(events)
    spans = [e for e in events if e.name != T.WINDOW_SPAN and
             e.name.startswith((T.SPAN_PREFIX, PROGRAM_PREFIX))]
    idle, by_op, scope_ns = defaultdict(float), defaultdict(float), \
        defaultdict(float)
    for ops in planes.values():
        inside = [dataclasses.replace(o, start=max(o.start, lo),
                                      dur=min(o.end, hi) - max(o.start, lo))
                  for o in ops if o.end > lo and o.start < hi]
        holes = T.gaps(T.union((o.start, o.end) for o in inside), lo, hi)
        names = innermost(spans, [(s + e) / 2 for s, e in holes])
        for (s, e), name in zip(holes, names):
            idle[_bare(name)] += (e - s) / len(planes)
        for o, own in T.self_times(inside):
            scope = getattr(o, "scope", "")
            by_op[f"{o.name}@{scope}" if scope else o.name] += \
                own / len(planes)
            if scope:
                scope_ns[scope] += own / len(planes)
    top = lambda d: [[k, v / 1e9] for k, v in            # noqa: E731
                     sorted(d.items(), key=lambda kv: -kv[1])[:T.TOP]]
    out.update(scope_s={k: v / 1e9 for k, v in sorted(scope_ns.items())},
               device_ops=top(by_op), idle_gaps=top(idle))
    return out


def _say(*parts) -> None:
    print(*parts, file=sys.stderr, flush=True)


def _report(spans, trace) -> None:
    durations = collections.defaultdict(list)
    for s in spans:
        durations[s.name].append((s.end_ns - s.start_ns) / 1e9)
    for name, secs in sorted(durations.items()):
        secs = sorted(secs)
        _say(f"span {PROGRAM_PREFIX}{name}: {len(secs)} x, median "
             f"{statistics.median(secs):.4f} s, sum {sum(secs):.4f} s, "
             f"longest {[round(x, 4) for x in secs[-3:]]} s")
    if trace is not None:
        _say(f"program scopes (device s): {trace['scope_s']}")
        _say(f"idle gaps by innermost span (s): {trace['idle_gaps']}")
        _say(f"device ops by scope (s): {trace['device_ops']}")


def view_of(run) -> types.SimpleNamespace:
    """The run's program spans (``spans``, the records handed back by
    ``stop_spans()``, empty for a program without them) and, for a
    traced run, its trace read again (``trace``, :func:`summarize`, or
    ``None``).  Read once per run, the first time a reader asks, and
    reported on standard error."""
    if not hasattr(run, "program"):
        stop = getattr(_program(), "stop_spans", None)
        spans = stop() if stop is not None else []
        trace = None
        if run.trace is not None and TRACES.is_dir():
            trace = summarize(read_events(TRACES))
        run.program = types.SimpleNamespace(spans=spans, trace=trace)
        _report(spans, trace)
    return run.program
