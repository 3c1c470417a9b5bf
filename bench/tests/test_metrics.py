"""Each per-layer reader, on a hand-made run: its arithmetic, and that
it returns nothing (never 0) when it has nothing to read; and how a
tagged metric name finds its reader and its window number."""
import json
import pathlib
import types

import pytest
import scipy.sparse as sp

from bench import loadgen as L
from bench import run
from bench.run import reader

BENCH = pathlib.Path(__file__).resolve().parents[1]
SPEC = json.loads((BENCH.parent / "BENCHMARK.json").read_text())


def view(trace=None, **win):
    a = sp.identity(1000, format="csr")                # nnz 1000, n 1000
    spans = L.Spans()
    return types.SimpleNamespace(
        win=L.Window(**win), spans=spans, a=a, trace=trace,
        cfg={"precision": {"value_bytes": 2, "vector_bytes": 4}},
        peaks={"hbm_bytes_per_s": 1e9})


TRACE = {"window_s": 10.0, "busy_s": 7.5, "kernel_s": {"spmv_ellpack": 0.5}}


@pytest.mark.parametrize("name", [m["name"] for m in SPEC["per_layer"]])
def test_every_metric_has_a_reader_that_can_say_nothing(name):
    assert reader(name).read(view()) is None


def test_roofline_share():
    answers = [L.Answer(None, None, "CONVERGED", 99)] * 2
    got = reader("spmv_ellpack_roofline").read(view(TRACE, answers=answers))
    need = 2 * 100 * (1000 * 4 + 2000 * 4)    # lane-SpMVs x least bytes
    assert got == pytest.approx(100 * need / 1e9 / 0.5)


def test_stream_bytes_per_nnz():
    got = reader("m1.stream_bytes_per_nnz").read(view(
        counters={"spmv_calls": 10, "bytes_streamed_est": 60000}))
    assert got == pytest.approx(6.0)


def test_useful_lane_iterations():
    calls = [{"lanes": 2, "iterations": [10, 5], "seconds": 1.0},
             {"lanes": 2, "iterations": [4, 4], "seconds": 1.0}]
    got = reader("vm.useful_lane_iter_pct").read(view(calls=calls))
    assert got == pytest.approx(100 * 23 / 28)


@pytest.mark.parametrize("name", sorted(
    m["name"] for m in SPEC["per_layer"]
    if m["name"].startswith("device_idle_pct")))
def test_idle_share(name):
    assert reader(name).read(view(TRACE)) == pytest.approx(25.0)


def test_tagged_names_fall_back_to_the_untagged():
    assert run.untagged("solves_per_s.hpcg", {"solves_per_s"}) == \
        "solves_per_s"
    assert run.untagged("a.b.c", {"a.b", "a"}) == "a.b"
    assert run.untagged("a.b", {"a.b", "a"}) == "a.b"
    with pytest.raises(KeyError):
        run.untagged("solves_per_s.hpcg", {"latency_p50_s"})
    assert reader("device_idle_pct.anything").__file__.endswith(
        "device_idle_pct.py")


@pytest.mark.parametrize("name", [m["name"] for m in SPEC["end_to_end"]])
def test_every_end_to_end_metric_has_a_window_number(name):
    win = L.Window(calls=[{"seconds": 1.0, "lanes": 2, "iterations": [1]}],
                   latencies=[1.0, 2.0])
    assert run.untagged(name, {**L.end_to_end(win), "setup_s": 1.0})


def test_admit_median_from_spans():
    v = view()
    v.spans.seconds["submit"] = [0.010, 0.030, 0.020]
    assert reader("engine.admit_ms").read(v) == pytest.approx(20.0)
