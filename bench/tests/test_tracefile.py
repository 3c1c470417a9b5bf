"""The trace reduction: union of device intervals, kernel time, idle
gaps named by the host span open over them -- on a hand-made trace with
known answers, and on a small trace recorded on a TPU v5e."""
import pathlib

import pytest

from bench import tracefile as T

FIXTURES = pathlib.Path(__file__).resolve().parent / "fixtures"
DEV, HOST = "/device:TPU:0", "/host:CPU"


def op(name, start, dur, plane=DEV):
    return T.Event(plane, T.DEVICE_OPS_LINE, name, start, dur)


def span(name, start, dur):
    return T.Event(HOST, "python", "bench." + name, start, dur)


HAND = [
    span("window", 0, 1000),
    span("generate", 0, 100),
    span("call", 100, 900),
    op("before-window", -50, 60),          # 10 ns inside the window
    op("while.1", 190, 630),               # holds the next three
    op("fusion.1", 200, 100),
    op("spmv_ellpack.18", 300, 50),
    op("fusion.2", 600, 200),
    op("after", 950, 100),                 # 50 ns inside the window
    T.Event(DEV, "XLA Modules", "module", 0, 1000),   # not an op line
]


def test_hand_trace():
    s = T.summarize(HAND, kernels=("spmv_ellpack",))
    assert s["window_s"] == pytest.approx(1000e-9)
    # busy: [0,10) [190,820) [950,1000)
    assert s["busy_s"] == pytest.approx(690e-9)
    assert s["kernel_s"]["spmv_ellpack"] == pytest.approx(50e-9)
    # gaps [10,190) (midpoint 100, where call opens) and [820,950)
    assert dict(s["idle_gaps"]) == {"call": pytest.approx(310e-9)}
    ops = dict(s["device_ops"])
    assert ops["while.1"] == pytest.approx(280e-9)      # self time
    assert ops["fusion.2"] == pytest.approx(200e-9)
    assert ops["before-window"] == pytest.approx(10e-9)
    assert ops["after"] == pytest.approx(50e-9)
    assert s["devices"] == 1
    assert sum(ops.values()) == pytest.approx(690e-9)


def test_short_name():
    assert T.short_name("%fusion.103 = f32[8]{0} fusion(f32[8] %a)") == \
        "fusion.103"
    assert T.short_name("%spmv_ellpack.18 = f32[1] custom-call()") == \
        "spmv_ellpack.18"


def test_gap_named_by_open_span():
    ev = [span("window", 0, 100), span("generate", 0, 40),
          span("call", 40, 60), op("x", 45, 10)]
    idle = dict(T.summarize(ev)["idle_gaps"])
    assert idle["generate"] == pytest.approx(45e-9)   # [0,45): mid 22.5
    assert idle["call"] == pytest.approx(45e-9)       # [55,100)


def test_busy_averaged_over_devices():
    ev = [span("window", 0, 100), op("a", 0, 100),
          op("b", 0, 50, plane="/device:TPU:1")]
    s = T.summarize(ev)
    assert s["busy_s"] == pytest.approx(75e-9) and s["devices"] == 2


def test_no_window_or_no_device_is_an_error():
    with pytest.raises(ValueError):
        T.summarize([op("a", 0, 1)])
    with pytest.raises(ValueError):
        T.summarize([span("window", 0, 10)])


def test_interval_helpers():
    assert T.union([(5, 7), (0, 2), (1, 3), (6, 9)]) == [(0, 3), (5, 9)]
    assert T.gaps([(2, 3), (5, 6)], 0, 8) == [(0, 2), (3, 5), (6, 8)]
    assert T.clip([(-5, 1), (2, 3), (9, 12)], 0, 10) == [(0, 1), (2, 3),
                                                         (9, 10)]


def test_fixture_roundtrip(tmp_path):
    p = tmp_path / "e.json.gz"
    T.save_events(HAND, str(p))
    assert T.load_events(str(p)) == HAND


@pytest.mark.parametrize("name,spans", [
    ("bag8_v5e", {"call", "generate"}),
    ("stream_v5e", {"submit", "step", "wait", "none"})])
def test_recorded_v5e_trace(name, spans):
    """1.3 s slices of ``--trace 1`` runs of the graph500 cells on one
    TPU v5e: the reduction's invariants on a real trace, where a
    ``while`` operation holds its body's operations."""
    ev = T.load_events(str(FIXTURES / f"{name}.events.json.gz"))
    s = T.summarize(ev, kernels=("spmv_ellpack", "spmv_sell"))
    assert s["window_s"] == pytest.approx(1.3)
    assert 0.5 * s["window_s"] < s["busy_s"] < s["window_s"]
    idle = dict(s["idle_gaps"])
    assert set(idle) <= spans
    assert sum(idle.values()) == pytest.approx(s["window_s"] - s["busy_s"])
    # the graph500 cells run the XLA SELL gather: no Pallas kernel
    assert s["kernel_s"] == {"spmv_ellpack": 0.0, "spmv_sell": 0.0}
    lo, hi = T.window_of(ev)
    inside = [o for o in T.device_ops(ev)[DEV] if lo <= o.start
              and o.end <= hi]
    assert any(o.name.startswith("while") for o in inside)
    # operations only overlap by nesting: self times add up to the union
    own = sum(t for _, t in T.self_times(inside))
    assert own == pytest.approx(sum(e - s for s, e in T.union(
        (o.start, o.end) for o in inside)), rel=1e-9)
    top = [n for n, _ in s["device_ops"]]
    assert top[0].startswith("fusion") and len(top) == T.TOP
