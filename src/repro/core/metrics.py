"""Solver health + observability primitives (ISSUE 9).

The paper's Challenge 1 is terminating acceleration *on the fly*; the
serving-scale completion of that challenge is terminating lanes that can
**never** get to ``‖r‖² ≤ τ_g``.  CG breaks down in two recognizable
ways (classic IC/JPCG folklore):

* **indefinite** — ``pAp ≤ 0``: the operand is not positive definite
  along the current search direction (an indefinite or singular matrix,
  or a matrix whose low-precision packing rounded it singular), so
  ``α = rz/pAp`` stops being a descent step;
* **non-finite** — ``rr``/``α``/``β`` leaves the reals (NaN/Inf seeded
  by the inputs, a zero pivot in the Jacobi divide, or overflow after an
  indefinite step slipped through at exactly 0).

Both engines (:mod:`repro.core.batch` phases, :mod:`repro.core.vm`
specialized + generic) evaluate :func:`tick_health` on each tick's
*candidate* values: a lane that trips a predicate **freezes that tick**
— its writes are discarded, its iteration counter does not advance, and
its ``status`` latches the breakdown code.  Healthy lanes see only
compares and ``where`` selects on values the tick already computed, so
detection is bit-invisible to them (asserted by ``tests/test_health.py``
against detection-off runs and the phases oracle).

Status lattice (terminal states are latched; ``RUNNING`` is the only
non-terminal value)::

    RUNNING ──> CONVERGED              rr ≤ τ on a committed tick
            ──> MAXITER                per-lane budget exhausted
            ──> BREAKDOWN_INDEFINITE   pAp ≤ 0 on the candidate tick
            ──> BREAKDOWN_NONFINITE    rr/α/β non-finite (or rr non-
                                       finite already at warm-up)

:class:`Metrics` is the observability counterpart: a plain counter bag
(snapshotable as a dict) used by :class:`repro.serve.SolverEngine`
(engine-owned instance, ``SolverEngine.metrics()``) and by
:func:`repro.core.batch.jpcg_solve_batched` (module-global instance,
:func:`solver_metrics`).

Span recorder: :func:`span` marks a host-side stretch of the solve path
(``batch.pack``, ``engine.admit.warm``, ...) and :func:`record` keeps a
finished interval after the fact (``engine.request``, from admission to
harvest).  Recording is off by default, and then :func:`span` returns
one shared no-op context: a flag test, no clock read, no allocation.
Between :func:`start_spans` and :func:`stop_spans` each span also opens
``jax.profiler.TraceAnnotation("repro." + name)``, so it lands in a
profiler trace on the clock the device operations are on, and is kept
in memory as a :class:`SpanRecord` on ``time.perf_counter_ns``.  No
span synchronizes with the device: one that ends before the device
finishes says so in its name (``launch``), and where the host blocks,
the pull that already blocks ends the span (``wait``).  The recorder
is process-wide and meant for one thread, the one driving the solver.
"""
from __future__ import annotations

import contextlib
import time
from collections import Counter
from typing import Dict, List, NamedTuple, Optional, Union

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["STATUS_RUNNING", "STATUS_CONVERGED", "STATUS_MAXITER",
           "STATUS_BREAKDOWN_INDEFINITE", "STATUS_BREAKDOWN_NONFINITE",
           "STATUS_NAMES", "BREAKDOWN_STATUSES", "status_name",
           "is_breakdown", "is_breakdown_codes", "initial_status",
           "tick_health",
           "advance_status", "finalize_status", "Metrics",
           "solver_metrics", "reset_solver_metrics", "SpanRecord",
           "span", "record", "start_spans", "stop_spans"]

# ------------------------------------------------------------- status codes
#: Lane still iterating (the only non-terminal status).
STATUS_RUNNING = 0
#: ``rr ≤ τ`` on a committed tick (or already at warm-up).
STATUS_CONVERGED = 1
#: Per-lane iteration budget exhausted without convergence.
STATUS_MAXITER = 2
#: ``pAp ≤ 0`` — operand not SPD along the search direction.
STATUS_BREAKDOWN_INDEFINITE = 3
#: ``rr``/``α``/``β`` went NaN/Inf (incl. non-finite warm-up ``rr``).
STATUS_BREAKDOWN_NONFINITE = 4

STATUS_NAMES: Dict[int, str] = {
    STATUS_RUNNING: "RUNNING",
    STATUS_CONVERGED: "CONVERGED",
    STATUS_MAXITER: "MAXITER",
    STATUS_BREAKDOWN_INDEFINITE: "BREAKDOWN_INDEFINITE",
    STATUS_BREAKDOWN_NONFINITE: "BREAKDOWN_NONFINITE",
}

#: The statuses the engine's fp64 escalation policy may retry.
BREAKDOWN_STATUSES = ("BREAKDOWN_INDEFINITE", "BREAKDOWN_NONFINITE")


def status_name(code: Union[int, str]) -> str:
    """Human-readable name of a status code (names pass through)."""
    if isinstance(code, str):
        return code
    return STATUS_NAMES.get(int(code), f"UNKNOWN({int(code)})")


def is_breakdown(status: Union[int, str, None]) -> bool:
    """True iff the status (code or name) is a breakdown exit."""
    if status is None:
        return False
    return status_name(status) in BREAKDOWN_STATUSES


def is_breakdown_codes(codes) -> np.ndarray:
    """Vectorized :func:`is_breakdown` over a host array of status codes."""
    codes = np.asarray(codes)
    return ((codes == STATUS_BREAKDOWN_INDEFINITE)
            | (codes == STATUS_BREAKDOWN_NONFINITE))


# --------------------------------------------------- in-loop status algebra
def initial_status(rr, tol, *, detect: bool):
    """Warm-up status vector from the initial ``rr`` (both engines).

    ``CONVERGED`` where ``rr ≤ tol`` already holds, else ``RUNNING``;
    with ``detect`` a non-finite warm-up ``rr`` (NaN/Inf-seeded operand
    or rhs) latches ``BREAKDOWN_NONFINITE`` immediately — such a lane is
    inactive from tick 0 either way (``NaN > tol`` is False), detection
    just names the reason instead of wearing the MAXITER face.
    """
    st = jnp.where(rr <= tol, STATUS_CONVERGED,
                   STATUS_RUNNING).astype(jnp.int32)
    if detect:
        st = jnp.where(~jnp.isfinite(rr), STATUS_BREAKDOWN_NONFINITE, st)
    return st


def tick_health(keep, pap, alpha, beta, rr_new, *, detect: bool):
    """Classify one tick's candidate scalars per lane.

    Returns ``(upd, bd_indef, bd_nonf)``: ``upd`` is the commit mask —
    lanes whose tick writes land (``keep`` minus fresh breakdowns);
    ``bd_*`` flag lanes that froze this tick (``None`` when ``detect``
    is off, in which case ``upd is keep`` — the caller's dataflow is
    unchanged *by construction*, which is what makes detection-off a
    bit-exact reference).  Precedence: ``pAp ≤ 0`` wins over non-finite
    (an indefinite step at exactly 0 makes ``α`` Inf in the same tick —
    the indefiniteness is the diagnosis, the Inf the symptom); NaN
    ``pAp`` fails the ``≤ 0`` compare and lands in non-finite.

    Assumes the tick computes ``pAp`` (every compiled ISA program and
    the phase engine do); a custom VM program that never writes the
    ``pap`` scalar register must run with detection off.
    """
    if not detect:
        return keep, None, None
    bd_indef = keep & (pap <= 0)
    bad = ~(jnp.isfinite(rr_new) & jnp.isfinite(alpha) & jnp.isfinite(beta))
    bd_nonf = keep & ~bd_indef & bad
    return keep & ~(bd_indef | bd_nonf), bd_indef, bd_nonf


def advance_status(status, *, upd, bd_indef, bd_nonf, rr_new, tol, it,
                   maxiter_vec=None):
    """One tick's status transitions (shared by both engines).

    ``it`` is the already-advanced per-lane count; ``maxiter_vec`` is
    the per-lane budget when the loop enforces one in-loop (the serving
    steppers — solve runners bound ``k`` statically instead and map
    leftover ``RUNNING`` via :func:`finalize_status`).  Terminal states
    latch: every transition is gated on a mask that is ``False`` for
    lanes already frozen.
    """
    if bd_indef is not None:
        status = jnp.where(bd_indef, STATUS_BREAKDOWN_INDEFINITE, status)
        status = jnp.where(bd_nonf, STATUS_BREAKDOWN_NONFINITE, status)
    conv = upd & (rr_new <= tol)
    status = jnp.where(conv, STATUS_CONVERGED, status)
    if maxiter_vec is not None:
        status = jnp.where(upd & ~conv & (it >= maxiter_vec),
                           STATUS_MAXITER, status)
    return status


def finalize_status(status):
    """Map leftover ``RUNNING`` to ``MAXITER`` when a solve runner's loop
    exits — the only ways to leave the loop still ``RUNNING`` are the
    static ``k == maxiter`` bound and (detection off) a lane inactive
    since warm-up, both of which wear the budget-exhausted face."""
    return jnp.where(status == STATUS_RUNNING, STATUS_MAXITER, status)


# ------------------------------------------------------------- observability
class Metrics:
    """Flat counter bag + exit-status histogram, snapshotable as a dict.

    Deliberately dumb: ``bump`` adds to named integer counters,
    ``record_exit`` feeds the status histogram, ``snapshot`` returns
    plain Python data (safe to json-dump next to BENCH_*.json).  All
    host-side — nothing here touches a traced value.
    """

    def __init__(self) -> None:
        self._counters: Counter = Counter()
        self._exits: Counter = Counter()

    def reset(self) -> None:
        self._counters.clear()
        self._exits.clear()

    def bump(self, name: str, value: int = 1) -> None:
        self._counters[name] += int(value)

    def record_exit(self, status: Union[int, str],
                    count: int = 1) -> None:
        self._exits[status_name(status)] += int(count)

    def record_exits(self, statuses) -> None:
        """Histogram a whole status vector (host array of codes)."""
        codes, counts = np.unique(np.asarray(statuses), return_counts=True)
        for c, n in zip(codes, counts):
            self.record_exit(int(c), int(n))

    def get(self, name: str) -> int:
        return int(self._counters.get(name, 0))

    @property
    def exit_histogram(self) -> Dict[str, int]:
        return dict(self._exits)

    def snapshot(self, extra: Optional[dict] = None) -> dict:
        out = {k: int(v) for k, v in sorted(self._counters.items())}
        out["exit_status"] = dict(self._exits)
        if extra:
            out.update(extra)
        return out


#: Module-global metrics fed by the solve runners
#: (:func:`repro.core.batch.jpcg_solve_batched`); the serving engine owns
#: its own instance instead (``SolverEngine.metrics()``).
_GLOBAL = Metrics()


def solver_metrics() -> Metrics:
    """The process-wide solver metrics instance."""
    return _GLOBAL


def reset_solver_metrics() -> None:
    _GLOBAL.reset()


# ------------------------------------------------------------------- spans
class SpanRecord(NamedTuple):
    """One finished span: ``perf_counter_ns`` bounds, the name of the
    span open around it when it began (``None`` at top level or for a
    :func:`record`), and the batch call's or request's id."""

    name: str
    start_ns: int
    end_ns: int
    parent: Optional[str]
    rid: Optional[int]


#: finished spans while recording, else None (recording off)
_RECORDS: Optional[List[SpanRecord]] = None
#: spans open now, innermost last
_OPEN: List["_Span"] = []
_OFF = contextlib.nullcontext()


class _Span:
    """A span while recording; ``with`` yields it (``start_ns`` is set)."""

    __slots__ = ("name", "rid", "parent", "start_ns", "_ann")

    def __init__(self, name: str, rid: Optional[int]):
        self.name, self.rid = name, rid

    def __enter__(self) -> "_Span":
        self._ann = jax.profiler.TraceAnnotation("repro." + self.name)
        self._ann.__enter__()
        self.parent = _OPEN[-1].name if _OPEN else None
        _OPEN.append(self)
        self.start_ns = time.perf_counter_ns()
        return self

    def __exit__(self, *exc) -> None:
        end = time.perf_counter_ns()
        _OPEN.pop()
        if _RECORDS is not None:
            _RECORDS.append(SpanRecord(self.name, self.start_ns, end,
                                       self.parent, self.rid))
        self._ann.__exit__(*exc)


def span(name: str, *, rid: Optional[int] = None):
    """Context manager marking ``name`` on the host (see the module
    docstring); yields the open span while recording, else ``None``."""
    if _RECORDS is None:
        return _OFF
    return _Span(name, rid)


def record(name: str, start_ns: int, end_ns: int,
           rid: Optional[int] = None) -> None:
    """Keep an interval measured elsewhere (in memory only, no parent)."""
    if _RECORDS is not None:
        _RECORDS.append(SpanRecord(name, start_ns, end_ns, None, rid))


def start_spans() -> None:
    """Start recording spans (dropping any records not yet handed back)."""
    global _RECORDS
    _RECORDS = []


def stop_spans() -> List[SpanRecord]:
    """Stop recording; returns the records since :func:`start_spans`
    (empty if recording was off)."""
    global _RECORDS
    out, _RECORDS = _RECORDS or [], None
    return out
