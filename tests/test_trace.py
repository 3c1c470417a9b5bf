"""The span recorder (``repro.core.metrics.span``): off it records
nothing and hands out one shared no-op context; on it nests; the batched
entry and the solver engine put their spans where their layers meet; and
recording moves no bit of any result."""
import numpy as np
import pytest

from repro.core import metrics as M
from repro.core.batch import jpcg_solve_batched
from repro.serve.solver_engine import SolverEngine, SolverEngineConfig
from repro.sparse import poisson_2d, powerlaw_spd
from tests.oracles import assert_results_bit_identical

#: auto layout picks row-ELL for the stencil and SELL for the skewed one
SYSTEMS = {"rowell": [poisson_2d(6), poisson_2d(5)],
           "sell": [powerlaw_spd(200, seed=1), powerlaw_spd(150, seed=2)]}
BATCH_CHILDREN = ["batch.prepare", "batch.launch", "batch.wait",
                  "batch.results"]


@pytest.fixture(autouse=True)
def recorder_off():
    M.stop_spans()
    yield
    M.stop_spans()


def recorded(fn):
    M.start_spans()
    try:
        out = fn()
    finally:
        spans = M.stop_spans()
    return out, spans


def solve(layout):
    csrs = SYSTEMS[layout]
    rng = np.random.default_rng(7)
    bs = [rng.standard_normal(a.shape[0]) for a in csrs]
    return jpcg_solve_batched(csrs, bs, tol=1e-16, maxiter=200,
                              scheme="mixed_v3", layout="auto")


def test_off_records_nothing():
    assert M.span("batch.solve") is M.span("engine.step", rid=3)
    with M.span("batch.solve", rid=1) as sp:
        assert sp is None
    M.record("engine.request", 1, 2, rid=1)
    assert M.stop_spans() == []


def test_on_nests_parents():
    def nest():
        with M.span("a", rid=1) as a:
            assert a.start_ns > 0
            with M.span("b"):
                pass
            with M.span("c"):
                with M.span("d", rid=2):
                    pass
        M.record("r", 5, 9, rid=4)

    _, spans = recorded(nest)
    got = [(s.name, s.parent, s.rid) for s in spans]
    assert got == [("b", "a", None), ("d", "c", 2), ("c", "a", None),
                   ("a", None, 1), ("r", None, 4)]
    by = {s.name: s for s in spans}
    for child in "bcd":
        parent = by[by[child].parent]
        assert parent.start_ns <= by[child].start_ns <= by[child].end_ns \
            <= parent.end_ns
    assert by["b"].end_ns <= by["c"].start_ns
    assert (by["r"].start_ns, by["r"].end_ns) == (5, 9)
    # stopped: off again
    assert M.span("a") is M.span("b")


@pytest.mark.parametrize("layout", sorted(SYSTEMS))
def test_batch_spans(layout):
    solve(layout)                       # compile outside the recording
    res, spans = recorded(lambda: solve(layout))
    assert all(r.status == "CONVERGED" for r in res)
    assert len({s.rid for s in spans}) == 1 and spans[0].rid is not None
    top = [s for s in spans if s.parent is None]
    assert [s.name for s in top] == ["batch.solve"]
    kids = [s.name for s in spans if s.parent == "batch.solve"]
    assert kids == BATCH_CHILDREN
    prep = [s.name for s in spans if s.parent == "batch.prepare"]
    assert prep == ["batch.layout", "batch.pack", "batch.put"]
    assert len(spans) == 1 + len(BATCH_CHILDREN) + len(prep)
    order = sorted((s for s in spans if s.parent == "batch.solve"),
                   key=lambda s: s.start_ns)
    assert [s.name for s in order] == BATCH_CHILDREN
    assert all(a.end_ns <= b.start_ns for a, b in zip(order, order[1:]))


def test_batch_layout_span_only_when_auto():
    csrs = SYSTEMS["sell"]
    _, spans = recorded(lambda: jpcg_solve_batched(
        csrs, tol=1e-16, maxiter=200, scheme="mixed_v3", layout="sell"))
    names = [s.name for s in spans]
    assert "batch.layout" not in names and "batch.pack" in names


@pytest.mark.parametrize("layout", sorted(SYSTEMS))
def test_batch_results_bit_identical_when_recording(layout):
    off = solve(layout)
    on, spans = recorded(lambda: solve(layout))
    assert spans
    assert_results_bit_identical(on, off, rr=True, status=True)


def run_engine(systems):
    eng = SolverEngine(SolverEngineConfig(batch_slots=4, chunk_iters=8,
                                          scheme="mixed_v3", tol=1e-16,
                                          maxiter=400))
    rng = np.random.default_rng(3)
    todo, rids = list(systems), []
    while todo:                         # admit while slots are free
        while todo and eng.free_slots():
            a = todo.pop(0)
            rids.append(eng.submit(a, rng.standard_normal(a.shape[0])))
        eng.step()
    eng.run_to_completion()
    return eng, rids


ENGINE_SYSTEMS = [poisson_2d(4), poisson_2d(9), poisson_2d(5),
                  poisson_2d(3), poisson_2d(7), poisson_2d(6)]


def test_engine_request_records():
    (eng, rids), spans = recorded(lambda: run_engine(ENGINE_SYSTEMS))
    assert sorted(eng.results) == rids
    reqs = [s for s in spans if s.name == "engine.request"]
    assert sorted(s.rid for s in reqs) == rids
    admits = {s.rid: s for s in spans if s.name == "engine.admit"}
    assert sorted(admits) == rids
    for r in reqs:
        assert r.start_ns == admits[r.rid].start_ns < r.end_ns
    submits = {s.rid: s for s in spans if s.name == "engine.submit"}
    assert sorted(submits) == rids
    assert all(a.parent == "engine.submit" for a in admits.values())
    for name, parent in [("engine.admit.pack", "engine.admit"),
                         ("engine.admit.warm", "engine.admit"),
                         ("engine.step.pull", "engine.step"),
                         ("engine.step.launch", "engine.step"),
                         ("engine.step.wait", "engine.step")]:
        got = [s for s in spans if s.name == name]
        assert got and all(s.parent == parent for s in got), name
    assert len([s for s in spans if s.name == "engine.admit.pack"]) == \
        len(ENGINE_SYSTEMS)
    harvests = {s.parent for s in spans if s.name == "engine.harvest"}
    assert harvests == {"engine.submit", "engine.step", None}
    compacts = [s for s in spans if s.name == "engine.compact"]
    assert len(compacts) == eng.metrics()["compactions"] > 0
    assert all(s.parent == "engine.step" for s in compacts)


def test_engine_results_bit_identical_when_recording():
    off, _ = run_engine(ENGINE_SYSTEMS)
    (on, _), spans = recorded(lambda: run_engine(ENGINE_SYSTEMS))
    assert spans and sorted(on.results) == sorted(off.results)
    for rid in off.results:
        assert_results_bit_identical([on.results[rid]], [off.results[rid]],
                                     rr=True, status=True)


def test_escalated_request_keeps_its_first_admission():
    """A request retried at fp64 is admitted twice under one id; its
    ``engine.request`` runs from the first admission to the harvest."""
    eps = 1e-9           # 1 - eps rounds to 1.0 in float32: singular
    a = np.array([[1.0, 1.0 - eps], [1.0 - eps, 1.0]])
    eng = SolverEngine(SolverEngineConfig(
        scheme="mixed_v3", batch_slots=4, chunk_iters=8,
        escalate_fp64=True))

    def run():
        rid = eng.submit(a, np.array([1.0, 0.0]), tol=1e-8, maxiter=50)
        return rid, eng.run_to_completion()[rid]

    (rid, res), spans = recorded(run)
    assert res.retried and res.converged
    admits = [s for s in spans if s.name == "engine.admit"]
    assert [s.rid for s in admits] == [rid, rid]
    assert admits[1].parent == "engine.harvest"
    (req,) = [s for s in spans if s.name == "engine.request"]
    assert req.rid == rid and req.start_ns == admits[0].start_ns
    assert req.end_ns > admits[1].end_ns


@pytest.mark.parametrize("scheme", ["tpu_v3_df", "tpu_v3"])
def test_replacement_scope_only_under_the_wide_scheme(scheme):
    """The replacement's device work is named ``vm_replace`` in the op
    metadata, and its SpMV ``spmv_ellpack_df``: both only where a wide
    scheme replaces residuals."""
    from repro.core.batch import _prepare, _runner
    from repro.core.precision import get_scheme
    sch = get_scheme(scheme)
    bag = _prepare([poisson_2d(6)], None, None, 1e-10, scheme=sch,
                   backend="pallas", layout="ellpack", bucket=True,
                   block_rows=256, col_tile=512, mesh=None, rid=0)
    _, make, args, _ = _runner(
        bag, engine="vm", policy=None, program=None, specialize=True,
        scheme=sch, backend="pallas", maxiter=50, with_trace=False,
        block_rows=256, col_tile=512, steps_per_sync=8, donate=False,
        detect=True, interpret=True, mesh=None)
    text = make().lower(*args).compile().as_text()
    wide = scheme == "tpu_v3_df"
    assert ("/vm_replace/" in text) == wide
    assert ("spmv_ellpack_df" in text) == wide
    assert "/vm_dot/" in text
