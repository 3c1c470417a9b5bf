"""The one traffic generator: reads a mix's data file and drives the system.

Every request solves the configuration's one operator for a right-hand
side drawn from the run's seed by the configuration's generator
(``bench/systems.py``); request ``j`` of a run gets right-hand side
``j``.  A mix (``bench/traffic/<name>.json``) names its ``loop``:

* ``closed`` -- back-to-back calls of ``jpcg_solve_batched`` on bags of
  ``lanes`` right-hand sides.  Calls run until the window has passed;
  the call in flight completes and counts.
* ``open`` -- requests arrive at ``rate_per_s`` into one
  ``SolverEngine``.  A run of ``seconds`` gets ``round(rate * seconds)``
  requests: Poisson arrivals given their count (uniform times, sorted),
  so that every seed offers the same number.  The generator keeps its
  own backlog and submits while the engine has a free slot, taking in
  what falls due meanwhile, then steps the engine; a request is timed
  from its due time to the return of the ``step()`` that harvested it.
  Arrivals stop at the end of the window; requests in flight drain for
  at most ``drain_s``.

The solver stops at ``||r|| <= rel_tol * ||b||`` on its recurrence
residual (the program takes the square of that, absolute).
"""
from __future__ import annotations

import collections
import contextlib
import dataclasses
import math
import time

import numpy as np

from bench import systems as S


#: seed of the right-hand sides that warm-up solves (no run uses it)
WARM_SEED = 1 << 40


class Spans:
    """Host spans on the host clock, also written into the profiler's
    trace as ``bench.<name>`` while ``traced``.  The benchmark opens one
    at a time inside the window, so they never overlap."""

    def __init__(self, traced: bool = False):
        self.traced = traced
        self.seconds = collections.defaultdict(list)

    @contextlib.contextmanager
    def __call__(self, name: str):
        ann = contextlib.nullcontext()
        if self.traced:
            import jax
            ann = jax.profiler.TraceAnnotation(f"bench.{name}")
        t0 = time.perf_counter()
        with ann:
            yield
        self.seconds[name].append(time.perf_counter() - t0)


@dataclasses.dataclass
class Answer:
    """One solve the window returned, kept for the reference."""
    b: np.ndarray
    x: np.ndarray
    status: str
    iterations: int


@dataclasses.dataclass
class Window:
    """What a measured window did."""
    seconds: float = 0.0
    attempted: int = 0
    answers: list = dataclasses.field(default_factory=list)
    missing: int = 0                 # requests that never came back
    calls: list = dataclasses.field(default_factory=list)   # closed loop
    latencies: list = dataclasses.field(default_factory=list)  # open loop
    lateness: list = dataclasses.field(default_factory=list)   # open loop
    backlog_at_close: int = 0        # open loop: waiting when arrivals end
    counters: dict = dataclasses.field(default_factory=dict)


def percentile(values, q: float) -> float:
    """Nearest-rank ``q``-quantile (``0 < q <= 1``) of all values."""
    v = sorted(values)
    if not v:
        raise ValueError("no values")
    return float(v[max(0, math.ceil(q * len(v)) - 1)])


def tol_of(b: np.ndarray, rel: float) -> float:
    """The program's absolute ``||r||^2`` target."""
    return rel * rel * float(b @ b)


def arrivals(traffic: dict, seed: int, seconds: float) -> np.ndarray:
    """Due times of an open-loop window's requests, sorted."""
    count = int(round(traffic["rate_per_s"] * seconds))
    return np.sort(S.rng_for(seed, S.STREAM_ARRIVALS).uniform(
        0.0, seconds, count))


def _csr(a):
    from repro.sparse import CSRMatrix
    return CSRMatrix(a.indptr.astype(np.int64), a.indices.astype(np.int32),
                     a.data, a.shape)


class ClosedLoop:
    """Back-to-back ``jpcg_solve_batched`` calls (see module docstring)."""

    def __init__(self, cfg: dict, traffic: dict, systems, solver: dict):
        self.cfg, self.traffic, self.systems = cfg, traffic, systems
        self.solver = solver
        self.csr = _csr(systems.a)
        self.lanes = traffic["lanes"]

    def _call(self, bs, tols):
        import repro.core as core
        s = self.solver
        return core.jpcg_solve_batched(
            [self.csr] * len(bs), bs, tol=tols, maxiter=s["maxiter"],
            scheme=s["scheme"], backend=s["backend"], layout=s["layout"],
            block_rows=s["block_rows"], col_tile=s["col_tile"])

    def warm(self) -> None:
        """One call, converged at admission: compiles (or loads) every
        program the window will run."""
        n = self.systems.a.shape[0]
        res = self._call([np.ones(n)] * self.lanes, [1e30] * self.lanes)
        [np.asarray(r.x) for r in res]

    def prepare(self, seed: int, seconds: float) -> None:
        pass

    def window(self, seed: int, seconds: float, spans: Spans) -> Window:
        import repro.core.metrics as pm
        win, rel = Window(), self.solver["rel_tol"]
        before = pm.solver_metrics().snapshot()
        t_start = time.perf_counter()
        k = 0
        while True:
            with spans("generate"):
                bs = [self.systems.rhs(seed, k * self.lanes + j)
                      for j in range(self.lanes)]
                tols = [tol_of(b, rel) for b in bs]
            with spans("call"):
                t0 = time.perf_counter()
                res = self._call(bs, tols)
                xs = [np.asarray(r.x) for r in res]
                t1 = time.perf_counter()
            win.calls.append({"seconds": t1 - t0, "lanes": len(bs),
                              "iterations": [r.iterations for r in res]})
            win.answers += [Answer(b, x, r.status, r.iterations)
                            for b, x, r in zip(bs, xs, res)]
            k += 1
            if t1 - t_start >= seconds:
                break
        win.seconds = time.perf_counter() - t_start
        win.attempted = len(win.answers)
        after = pm.solver_metrics().snapshot()
        win.counters = {key: after.get(key, 0) - before.get(key, 0)
                        for key in after if isinstance(after[key], int)}
        return win

    def close(self) -> None:
        import repro.core.batch as batch
        batch.batch_cache_clear()


class OpenLoop:
    """Requests arriving over time into one ``SolverEngine``."""

    def __init__(self, cfg: dict, traffic: dict, systems, solver: dict):
        from repro.serve import SolverEngine, SolverEngineConfig
        self.cfg, self.traffic, self.systems = cfg, traffic, systems
        self.solver = solver
        self.csr = _csr(systems.a)
        e = traffic["engine"]
        self.engine = SolverEngine(SolverEngineConfig(
            batch_slots=e["batch_slots"], chunk_iters=e["chunk_iters"],
            scheme=solver["scheme"], maxiter=solver["maxiter"],
            backend=solver["backend"], layout=solver["layout"],
            block_rows=solver["block_rows"], col_tile=solver["col_tile"]))
        self.plan = None

    def _drain(self, rids, max_steps: int = 1000) -> None:
        eng = self.engine
        for _ in range(max_steps):
            if all(r in eng.results for r in rids):
                return
            eng.step()
        raise RuntimeError(f"warm-up requests {rids} did not come back")

    def warm(self) -> None:
        """One request converged at admission, so the pool's bucket
        reaches the shape it keeps; then a full wave of real solves
        through ``step()`` fills every slot and compiles the stepper."""
        eng, rel = self.engine, self.solver["rel_tol"]
        eng.submit(self.csr, tol=1e30)
        eng.step()
        wave = []
        for k in range(eng.free_slots()):
            b = self.systems.rhs(WARM_SEED, k)
            wave.append(eng.submit(self.csr, b, tol=tol_of(b, rel)))
        self._drain(wave)
        eng.results.clear()
        eng.metrics()

    def prepare(self, seed: int, seconds: float) -> None:
        """Arrival times and right-hand sides of the window."""
        due = arrivals(self.traffic, seed, seconds)
        rel = self.solver["rel_tol"]
        bs = [self.systems.rhs(seed, j) for j in range(len(due))]
        self.plan = (due, bs, [tol_of(b, rel) for b in bs])

    def window(self, seed: int, seconds: float, spans: Spans) -> Window:
        eng, win = self.engine, Window()
        due, bs, tols = self.plan
        before = eng.metrics()
        backlog, pending, done_at = collections.deque(), {}, {}
        t_start = time.perf_counter()
        nxt, drain_end = 0, seconds + self.traffic["drain_s"]
        while True:
            while True:              # admit what is due while slots are free
                now = time.perf_counter() - t_start
                while nxt < len(due) and due[nxt] <= now:
                    backlog.append(nxt)
                    nxt += 1
                if not (backlog and eng.free_slots() > 0):
                    break
                j = backlog.popleft()
                with spans("submit"):
                    rid = eng.submit(self.csr, bs[j], tol=tols[j])
                pending[rid] = j
            if pending:
                with spans("step"):
                    eng.step()
                t = time.perf_counter() - t_start
                for rid in [r for r in pending if r in eng.results]:
                    done_at[pending.pop(rid)] = (rid, t)
            elif nxt < len(due):
                with spans("wait"):
                    delay = due[nxt] - (time.perf_counter() - t_start)
                    if delay > 0:
                        time.sleep(delay)
                    win.lateness.append(
                        time.perf_counter() - t_start - due[nxt])
            else:
                break
            if now > drain_end:
                break
            if now < seconds:
                win.backlog_at_close = len(backlog) + len(due) - nxt
        win.seconds = time.perf_counter() - t_start
        win.attempted = len(due)
        for j in range(len(due)):
            if j in done_at:
                rid, t = done_at[j]
                r = eng.results[rid]
                win.latencies.append(t - due[j])
                win.answers.append(Answer(bs[j], np.asarray(r.x), r.status,
                                          r.iterations))
            else:
                win.latencies.append(math.inf)
                win.missing += 1
        after = eng.metrics()
        win.counters = {key: after.get(key, 0) - before.get(key, 0)
                        for key in after if isinstance(after[key], int)}
        return win

    def close(self) -> None:
        import repro.core.batch as batch
        self.engine = None
        batch.batch_cache_clear()


#: the loops a mix's ``loop`` names; a new mix is a data file for one of
#: them
LOOPS = {"closed": ClosedLoop, "open": OpenLoop}


def end_to_end(win: Window) -> dict:
    """The end-to-end numbers a window gives, by metric name."""
    out = {}
    if win.calls:
        out["solves_per_s"] = (sum(c["lanes"] for c in win.calls)
                               / sum(c["seconds"] for c in win.calls))
    if win.latencies:
        out["latency_p50_s"] = percentile(win.latencies, 0.50)
        out["latency_p90_s"] = percentile(win.latencies, 0.90)
    return out
