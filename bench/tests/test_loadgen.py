"""The traffic generator's arithmetic, and the open loop's backlog and
drain rule against a fake engine: no program, no device."""
import math
import time
import types

import numpy as np
import pytest

from bench import loadgen as L

STREAM = {"loop": "open", "rate_per_s": 5.0, "drain_s": 0.5}
BIG = 2 ** 33 + 12345


def test_poisson_arrivals_from_seed():
    due = L.arrivals(STREAM, BIG, 20.0)
    again = L.arrivals(STREAM, BIG, 20.0)
    other = L.arrivals(STREAM, BIG + 1, 20.0)
    assert len(due) == 100 and np.array_equal(due, again)
    assert (np.diff(due) >= 0).all() and due[0] >= 0 and due[-1] < 20.0
    assert not np.array_equal(due, other) and len(other) == 100
    # exponential gaps: mean near 1 / rate, coefficient of variation ~1
    gaps = np.diff(due)
    assert 0.12 < gaps.mean() < 0.28 and 0.6 < gaps.std() / gaps.mean() < 1.4


def test_closed_loop_numbers_right_hand_sides(monkeypatch):
    """Call ``k`` solves right-hand sides ``k * lanes ...``; calls go on
    until the window has passed, and the last one counts."""
    import repro.core as core
    seen = []

    def fake(problems, bs, **kw):
        time.sleep(0.05)
        seen.append([int(b[0]) for b in bs])
        return [types.SimpleNamespace(x=b, status="CONVERGED", iterations=2)
                for b in bs]

    monkeypatch.setattr(core, "jpcg_solve_batched", fake)
    systems = types.SimpleNamespace(
        a=types.SimpleNamespace(indptr=np.zeros(2), indices=np.zeros(0),
                                data=np.zeros(0), shape=(1, 1)),
        rhs=lambda seed, j: np.array([float(j)]))
    loop = L.ClosedLoop.__new__(L.ClosedLoop)
    loop.systems, loop.lanes, loop.csr = systems, 3, None
    loop.solver = {"rel_tol": 1e-5, "maxiter": 9, "scheme": "s",
                   "backend": "b", "layout": "l", "block_rows": 1,
                   "col_tile": 1}
    win = loop.window(7, 0.12, L.Spans())
    assert seen[:3] == [[0, 1, 2], [3, 4, 5], [6, 7, 8]]
    assert win.attempted == 3 * len(seen) == len(win.answers)
    assert sum(c["seconds"] for c in win.calls) >= 0.12


@pytest.mark.parametrize("q,want", [(0.5, 50), (0.9, 90), (1.0, 100),
                                    (0.01, 1)])
def test_percentile_nearest_rank(q, want):
    vals = list(range(100, 0, -1))
    assert L.percentile(vals, q) == want


def test_percentile_counts_missing_as_inf():
    assert L.percentile([1.0] * 9 + [math.inf], 0.9) == 1.0
    assert L.percentile([1.0] * 8 + [math.inf] * 2, 0.9) == math.inf


class FakeEngine:
    """Two slots; a request finishes on the ``finish_after``-th step
    after its admission (never, if None)."""

    def __init__(self, slots=2, finish_after=1, step_s=0.05):
        self.slots, self.finish_after, self.step_s = slots, finish_after, \
            step_s
        self.live, self.results, self.next = {}, {}, 0
        self.submitted = []

    def free_slots(self):
        return self.slots - len(self.live)

    def submit(self, a, b, tol):
        if not self.free_slots():
            raise RuntimeError("no free solver slots")
        rid, self.next = self.next, self.next + 1
        self.live[rid] = 0
        self.submitted.append(rid)
        return rid

    def step(self):
        import time
        time.sleep(self.step_s)
        for rid in list(self.live):
            self.live[rid] += 1
            if self.finish_after and self.live[rid] >= self.finish_after:
                del self.live[rid]
                self.results[rid] = types.SimpleNamespace(
                    x=np.zeros(1), status="CONVERGED", iterations=3)

    def metrics(self):
        return {"admits": len(self.submitted)}


def open_loop(engine, traffic):
    loop = L.OpenLoop.__new__(L.OpenLoop)
    loop.traffic, loop.engine = traffic, engine
    loop.csr = None
    due = np.array([0.0, 0.0, 0.0, 0.01, 0.3])
    loop.plan = (due, [np.full(1, j) for j in range(5)], [1.0] * 5)
    return loop, due


def test_open_loop_backlog_and_latency():
    eng = FakeEngine(slots=2, finish_after=1, step_s=0.05)
    loop, due = open_loop(eng, dict(STREAM))
    win = loop.window(0, 0.4, L.Spans())
    assert win.attempted == 5 and win.missing == 0
    assert sorted(eng.submitted) == list(range(5))
    assert len(win.answers) == 5
    # two slots: the third request waits a step, the fourth too
    lat = win.latencies
    assert lat[0] == pytest.approx(0.05, abs=0.03)
    assert lat[2] == pytest.approx(0.10, abs=0.04)
    assert all(x > 0 for x in lat)
    assert [int(a.b[0]) for a in win.answers] == [0, 1, 2, 3, 4]


def test_open_loop_drain_bound_counts_missing():
    eng = FakeEngine(slots=8, finish_after=None, step_s=0.02)
    loop, _ = open_loop(eng, dict(STREAM, drain_s=0.2))
    win = loop.window(0, 0.4, L.Spans())
    assert win.missing == 5 and not win.answers
    assert all(math.isinf(x) for x in win.latencies)
    assert 0.6 <= win.seconds < 1.0
    e2e = L.end_to_end(win)
    assert math.isinf(e2e["latency_p90_s"])


def test_closed_loop_rate_over_call_time():
    win = L.Window(calls=[{"seconds": 2.0, "lanes": 8, "iterations": [1]},
                          {"seconds": 2.0, "lanes": 8, "iterations": [1]}])
    assert L.end_to_end(win) == {"solves_per_s": 4.0}
