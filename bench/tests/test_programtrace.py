"""The program's spans and device scopes in a trace (``bench/programtrace.py``)
and the readers built on them: idle gaps named by the innermost span,
bench's or program's; device time by scope, read from the op metadata of
an ``.xplane.pb``; nothing ``bench/tracefile.py`` reports moves; and each
new reader's arithmetic, on hand-made runs, on a recorded v5e trace and
on a small run of the program on the CPU."""
import json
import pathlib
import struct
import types

import pytest
import scipy.sparse as sp

from bench import loadgen as L
from bench import programtrace as P
from bench import run
from bench import systems as S
from bench import tracefile as T
from bench.run import reader

ROOT = pathlib.Path(__file__).resolve().parents[2]
FIXTURES = pathlib.Path(__file__).resolve().parent / "fixtures"
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
DEV, HOST = "/device:TPU:0", "/host:CPU"
NEW = ("batch.prepare_ms.hpcg", "batch.prepare_ms.graph500",
       "engine.request_p90_s", "spmv_xla_sell_roofline")


def op(name, start, dur, scope=""):
    return P.Event(DEV, T.DEVICE_OPS_LINE, name, start, dur, scope)


def span(name, start, dur):
    return P.Event(HOST, "python", name, start, dur)


HAND = [
    span("bench.window", 0, 1000),
    span("bench.generate", 0, 100),
    span("bench.call", 100, 850),
    span("repro.batch.solve", 110, 780),
    span("repro.batch.prepare", 110, 390),
    span("repro.batch.pack", 120, 280),
    span("repro.batch.put", 400, 100),
    span("repro.batch.launch", 500, 10),
    span("repro.batch.wait", 510, 370),
    op("copy.1", 50, 70),                              # in generate
    op("while.1", 520, 350),                           # holds the next two
    op("fusion.1", 530, 100, "m1_xla_sell"),
    op("spmv_ellpack.19", 700, 50, "vm_dot"),
    op("fusion.2", 800, 40, "m1_xla_sell"),
]


def test_hand_trace_names_gaps_by_innermost_span():
    s = P.summarize(HAND, kernels=("spmv_ellpack",))
    ref = T.summarize(HAND, kernels=("spmv_ellpack",))
    for key in ("window_s", "busy_s", "devices", "kernel_s"):
        assert s[key] == ref[key], key
    # gaps [0,50) mid 25: generate; [120,520) mid 320: batch.pack inside
    # prepare, solve and call; [870,1000) mid 935: call, no program span
    assert dict(s["idle_gaps"]) == {"generate": pytest.approx(50e-9),
                                    "batch.pack": pytest.approx(400e-9),
                                    "call": pytest.approx(130e-9)}
    assert dict(ref["idle_gaps"]) == {"generate": pytest.approx(50e-9),
                                      "call": pytest.approx(530e-9)}
    ops = dict(s["device_ops"])
    assert ops["fusion.1@m1_xla_sell"] == pytest.approx(100e-9)
    assert ops["spmv_ellpack.19@vm_dot"] == pytest.approx(50e-9)
    assert ops["while.1"] == pytest.approx(160e-9)      # self time
    assert s["scope_s"] == {"m1_xla_sell": pytest.approx(140e-9),
                            "vm_dot": pytest.approx(50e-9)}
    # a scope renames nothing the kernel reduction matches
    assert s["kernel_s"] == {"spmv_ellpack": pytest.approx(50e-9)}


def test_innermost():
    spans = [span("a", 0, 100), span("b", 10, 20), span("c", 40, 50),
             span("d", 45, 5), span("e", 200, 10)]
    points = [5, 15, 30, 46, 60, 95, 150, 205, 300]
    assert P.innermost(spans, points) == [
        "a", "b", "a", "d", "c", "a", None, "e", None]


def test_scope_of():
    assert P.scope_of("jit(run_spec)/while/body/while/body/closed_call/"
                      "m1_xla_sell/vmap()/gather:") == "m1_xla_sell"
    assert P.scope_of("jit(step)/m1_xla_rowell/vm_dot/reduce_sum:") == \
        "vm_dot"
    assert P.scope_of("jit(run)/while/body/add:") == ""
    assert P.scope_of("") == ""
    # the kernel reduction matches these substrings in op names
    assert not [s for s in P.SCOPES if "spmv_ellpack" in s
                or "spmv_sell" in s]


# ----------------------------------------------------- XSpace, by hand
def _varint(v):
    out = b""
    while True:
        b, v = v & 0x7F, v >> 7
        out += bytes([b | (0x80 if v else 0)])
        if not v:
            return out


def _f(num, value):
    """One protobuf field: an int as a varint, a float as fixed64,
    bytes/str length-delimited."""
    if isinstance(value, int):
        return _varint(num << 3) + _varint(value)
    if isinstance(value, float):
        return _varint(num << 3 | 1) + struct.pack("<d", value)
    if isinstance(value, str):
        value = value.encode()
    return _varint(num << 3 | 2) + _varint(len(value)) + value


def _entry(key, msg):
    return _f(1, key) + _f(2, msg)


def _plane(name, events, stats):
    body = _f(2, name) + _f(3, _f(2, "XLA Ops") + _f(4, _f(1, 10)))
    for mid, (op_name, stat_list) in events.items():
        meta = _f(1, mid) + _f(2, op_name)
        for st in stat_list:
            meta += _f(5, b"".join(_f(k, v) for k, v in st))
        body += _f(4, _entry(mid, meta))
    for sid, sname in stats.items():
        body += _f(5, _entry(sid, _f(1, sid) + _f(2, sname)))
    return _f(1, body)


def test_op_names_from_xspace(tmp_path):
    stats = {1: "tf_op", 2: "flops", 3: "jit(f)/vm_dot/reduce_sum:",
             4: "hlo_category"}
    dev = {10: ("%fusion.1 = f32[8] fusion()",
                [[(1, 1), (5, "jit(f)/while/body/m1_xla_sell/gather:")],
                 [(1, 2), (4, 7)]]),
           11: ("%copy.2 = f32[8] copy()", [[(1, 1), (7, 3)],
                                            [(1, 4), (2, 0.5)]]),
           12: ("%add.3 = f32[8] add()", [[(1, 2), (4, 1)]])}
    host = {5: ("repro.batch.pack", [[(1, 1), (5, "not a device op")]])}
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(_plane(DEV, dev, stats) + _plane(HOST, host, stats)
                     + _f(4, "hostname"))
    assert P.op_names(path) == {DEV: {
        "%fusion.1 = f32[8] fusion()":
            "jit(f)/while/body/m1_xla_sell/gather:",
        "%copy.2 = f32[8] copy()": "jit(f)/vm_dot/reduce_sum:"}}


# ------------------------------------------------- recorded v5e traces
ACCEPTED = ("bag8_v5e", "stream_v5e")
FIXTURE_NAMES = ACCEPTED + ("stream_spans_v5e",)


def trace_view(trace):
    answers = [L.Answer(None, None, "CONVERGED", 9)] * 8
    return types.SimpleNamespace(
        win=L.Window(answers=answers), spans=L.Spans(), trace=trace,
        a=sp.identity(1000, format="csr"),
        cfg={"precision": {"value_bytes": 2, "vector_bytes": 4}},
        peaks={"hbm_bytes_per_s": 819e9},
        program=types.SimpleNamespace(spans=[], trace=None))


@pytest.mark.parametrize("name", FIXTURE_NAMES)
def test_recorded_trace_reads_as_before(name):
    """Every number ``bench/tracefile.py`` gives, and every reader of
    the accepted benchmark that reads the trace, is the same whether the
    trace is read with the program's spans and scopes or without."""
    path = str(FIXTURES / f"{name}.events.json.gz")
    kernels = ("spmv_ellpack", "spmv_sell")
    events = P.load_events(path)
    # tracefile's own loader reads rows without a scope: the fixtures of
    # the accepted benchmark
    if name in ACCEPTED:
        old = T.summarize(T.load_events(path), kernels=kernels)
    else:
        old = T.summarize(events, kernels=kernels)
    new = P.summarize(events, kernels=kernels)
    for key in ("window_s", "busy_s", "devices", "kernel_s"):
        assert new[key] == old[key], key
    assert sum(v for _, v in new["idle_gaps"]) == pytest.approx(
        sum(v for _, v in old["idle_gaps"]), rel=1e-12)
    readers = [m["name"] for m in SPEC["per_layer"] if m["name"] not in NEW]
    for metric in readers:
        r = reader(metric)
        assert r.read(trace_view(new)) == r.read(trace_view(old)), metric


@pytest.mark.parametrize("name,busy", [("bag8_v5e", 1.159454145),
                                       ("stream_v5e", 1.051581532)])
def test_accepted_fixtures_unchanged(name, busy):
    """The recorded traces of the accepted benchmark hold no program
    span or scope, so the new reading names and counts them alike."""
    path = str(FIXTURES / f"{name}.events.json.gz")
    old = T.summarize(T.load_events(path))
    new = P.summarize(P.load_events(path))
    assert new["busy_s"] == pytest.approx(busy, rel=1e-9)
    assert new["idle_gaps"] == old["idle_gaps"]
    assert new["device_ops"] == old["device_ops"]
    assert new["scope_s"] == {}


def test_recorded_v5e_trace_with_program_spans():
    """A 1.3 s slice of a ``--trace 1`` run of ``graph500_s15.stream``
    on one TPU v5e, with the program's spans and scopes: a tick's end,
    its harvest, eight admissions and the next tick's launch."""
    ev = P.load_events(str(FIXTURES / "stream_spans_v5e.events.json.gz"))
    s = P.summarize(ev, kernels=("spmv_ellpack", "spmv_sell"))
    old = T.summarize(ev, kernels=("spmv_ellpack", "spmv_sell"))
    assert s["window_s"] == pytest.approx(1.3)
    assert 0.5 * s["window_s"] < s["busy_s"] < s["window_s"]
    idle = dict(s["idle_gaps"])
    assert sum(idle.values()) == pytest.approx(s["window_s"] - s["busy_s"])
    # the bench's submit gap splits into the engine's own spans
    assert set(dict(old["idle_gaps"])) == {"submit", "step"}
    assert {"engine.admit.pack", "engine.admit.warm",
            "engine.harvest"} <= set(idle) <= {
        "engine.admit.pack", "engine.admit.warm", "engine.harvest",
        "engine.step", "engine.step.wait", "engine.step.launch"}
    assert s["kernel_s"] == {"spmv_ellpack": 0.0, "spmv_sell": 0.0}
    top = [n for n, _ in s["device_ops"]]
    assert len(top) == T.TOP and all(n.startswith("fusion")
                                     for n in top[:4])
    assert all(n.endswith("@m1_xla_sell") for n in top[:4])
    # the gather is nearly all the device's time; the VM's modules show
    assert s["scope_s"]["m1_xla_sell"] > 0.95 * s["busy_s"]
    assert {"vm_dot", "vm_ctrl"} <= set(s["scope_s"])
    lo, hi = T.window_of(ev)
    inside = [o for o in T.device_ops(ev)[DEV] if lo <= o.start
              and o.end <= hi]
    own = sum(t for _, t in T.self_times(inside))
    assert own == pytest.approx(sum(e - s for s, e in T.union(
        (o.start, o.end) for o in inside)), rel=1e-9)


# ------------------------------------------------------------- readers
def records(*rows):
    from repro.core.metrics import SpanRecord
    return [SpanRecord(name, int(a * 1e9), int(b * 1e9), None, rid)
            for name, a, b, rid in rows]


def test_prepare_median_per_call():
    v = trace_view(None)
    v.program.spans = records(("batch.prepare", 0, 0.3, 0),
                              ("batch.pack", 0, 0.25, 0),
                              ("batch.prepare", 1, 1.5, 1),
                              ("batch.prepare", 2, 2.4, 2))
    for name in NEW[:2]:
        assert reader(name).__file__.endswith("batch.prepare_ms.py")
        assert reader(name).read(v) == pytest.approx(400.0)


def test_request_p90():
    v = trace_view(None)
    v.program.spans = records(*[("engine.request", 10, 10 + k, k)
                                for k in range(1, 11)],
                              ("engine.admit", 0, 50, 0))
    assert reader("engine.request_p90_s").read(v) == pytest.approx(9.0)


def test_xla_sell_roofline():
    v = trace_view(None)
    v.program.trace = {"scope_s": {"m1_xla_sell": 0.5, "vm_dot": 9.0}}
    v.peaks = {"hbm_bytes_per_s": 1e9}
    got = reader("spmv_xla_sell_roofline").read(v)
    need = 8 * 10 * (1000 * 4 + 2000 * 4)     # lane-SpMVs x least bytes
    assert got == pytest.approx(100 * need / 1e9 / 0.5)
    v.program.trace = {"scope_s": {"vm_dot": 9.0}}
    assert reader("spmv_xla_sell_roofline").read(v) is None


@pytest.mark.parametrize("name", NEW)
def test_new_metric_entries(name):
    (m,) = [m for m in SPEC["per_layer"] if m["name"] == name]
    assert m["workloads"] and set(m["workloads"]) <= {
        w["name"] for w in SPEC["workloads"]}
    cells = {c: run.metrics_of(SPEC, c, "end_to_end")
             for c in m["workloads"]}
    assert all(m["moves"] in {e["name"] for e in es}
               for es in cells.values())


def test_view_reads_the_trace_once(monkeypatch, tmp_path):
    """A traced run's trace is read again, with scopes, the first time a
    reader asks, and reported on standard error."""
    events = P.load_events(str(FIXTURES / "stream_spans_v5e.events.json.gz"))
    reads = []
    monkeypatch.setattr(P, "TRACES", tmp_path)
    monkeypatch.setattr(P, "read_events",
                        lambda d: reads.append(d) or events)
    v = types.SimpleNamespace(trace={"window_s": 1.3})
    got = P.view_of(v)
    assert P.view_of(v) is got and reads == [tmp_path]
    assert got.trace["scope_s"]["m1_xla_sell"] > 0


def test_a_program_without_the_recorder_gives_nothing(monkeypatch):
    """Laid over an older program, the readers switch nothing on and
    read nothing, and raise nothing."""
    monkeypatch.setattr(P, "_program", lambda: types.SimpleNamespace())
    for name in NEW:
        r = reader(name)                            # arms: a no-op here
        assert r.read(types.SimpleNamespace(trace=None)) is None


# ------------------------------------------- the program, on the CPU
def small(cell):
    """The cell's configuration overrides at its generator's ``SMALL``."""
    gen = run.config_of(run.cell_of(SPEC, cell))["generator"]
    return S.generator(gen).SMALL


@pytest.mark.parametrize("cell,metric", [
    ("hpcg_104.rhs2", "batch.prepare_ms.hpcg"),
    ("graph500_s15.bag8", "batch.prepare_ms.graph500"),
    ("graph500_s15.stream", "engine.request_p90_s")])
def test_reader_on_a_small_window(cell, metric):
    """Loading the reader switches the program's recorder on; a window
    of the cell's traffic then leaves spans that the reader reads."""
    built = run.setup(cell, require_tpu=False,
                      overrides={"cfg": small(cell),
                                 "traffic": {"drain_s": 3.0}})
    r = reader(metric)
    built.loop.prepare(5, 1.0)
    win = built.loop.window(5, 1.0, L.Spans())
    built.loop.close()
    v = types.SimpleNamespace(win=win, trace=None)
    got = r.read(v)
    assert got is not None and got > 0
    names = {s.name for s in v.program.spans}
    if metric.startswith("batch"):
        assert {"batch.solve", "batch.pack", "batch.put"} <= names
        assert got < 1000 * sum(c["seconds"] for c in win.calls)
    else:
        assert {"engine.admit.pack", "engine.step.wait"} <= names
        assert got <= max(win.latencies) + 1e-3
    # recording stopped with the read: the next window records nothing
    from repro.core import metrics as M
    assert M.span("batch.solve") is M.span("engine.step")


# ------------------------------------------ the result line's breakdown
def run_view(events, monkeypatch, tmp_path, spans):
    """A traced run's view as ``bench/run.py`` builds it from ``events``,
    with its per-layer readers, and a program whose recorder hands back
    ``spans``."""
    monkeypatch.setattr(P, "TRACES", tmp_path)
    monkeypatch.setattr(P, "read_events", lambda d: events)
    monkeypatch.setattr(P, "_program", lambda: types.SimpleNamespace(
        start_spans=lambda: None, stop_spans=lambda: list(spans)))
    readers = {m["name"]: reader(m["name"]) for m in SPEC["per_layer"]}
    kernels = sorted({k for r in readers.values()
                      for k in getattr(r, "KERNELS", ())})
    v = trace_view(T.summarize(events, kernels=kernels))
    del v.program
    return v, readers


def test_breakdown_names_gaps_by_program_span(monkeypatch, tmp_path):
    """On the recorded stream trace with the program's spans, the result
    line's gaps split the same idle time as the ``bench/`` spans do, its
    largest gap is the engine's admission and not the benchmark's
    ``submit``, and no per-layer number moves."""
    events = P.load_events(str(FIXTURES / "stream_spans_v5e.events.json.gz"))
    v, readers = run_view(events, monkeypatch, tmp_path,
                          records(("engine.admit", 0, 1, 0)))
    old = {name: r.read(v) for name, r in readers.items()}
    got = run.breakdown(v)
    assert {name: r.read(v) for name, r in readers.items()} == old
    renamed = trace_view(P.summarize(events, kernels=sorted(v.trace[
        "kernel_s"])))
    renamed.program = v.program
    assert {name: r.read(renamed) for name, r in readers.items()} == old
    assert abs(sum(s for _, s in got["idle_gaps"]) -
               sum(s for _, s in v.trace["idle_gaps"])) < 1e-9
    assert got["idle_gaps"][0][0] in ("engine.admit.pack",
                                      "engine.admit.warm")
    assert "submit" not in dict(got["idle_gaps"])
    assert got["device_ops"][0][0].endswith("@m1_xla_sell")
    assert got == {k: v.program.trace[k] for k in ("device_ops",
                                                   "idle_gaps")}


@pytest.mark.parametrize("name", ["bag8_v5e", "stream_v5e"])
def test_breakdown_without_program_spans_is_the_bench_view(
        name, monkeypatch, tmp_path):
    """The accepted benchmark's recorded traces hold no program span: the
    result line's breakdown names them as ``bench/tracefile.py`` does."""
    events = P.load_events(str(FIXTURES / f"{name}.events.json.gz"))
    v, _ = run_view(events, monkeypatch, tmp_path, [])
    assert run.breakdown(v) == {"device_ops": v.trace["device_ops"],
                                "idle_gaps": v.trace["idle_gaps"]}
