"""Batched stream VM — executes stream-centric ISA programs (paper §3–§4)
for G independent systems at once, inside one compiled loop.

The VM models Callipepla's top architecture (paper Fig. 1), widened by a
lane dimension so it can serve the batched solver and the serving engine
directly — this is the *single solver backend*; the phase-fused loop in
:mod:`repro.core.phases` remains as its bit-exact oracle:

* **memory** — the HBM vector buffers (x, r, p, ap, M, b) as one
  ``[6, G, n]`` array: buffer id × lane × element;
* **queues** — the inter-module FIFOs, ``[8, G, n]``; a queue register
  holds one logical vector in flight per lane (fan-out is free, like the
  paper's VecCtrl element duplication);
* **computation modules** M1–M8 — M1 routes through the same batched
  SpMV closures as the phase engine
  (:func:`repro.core.batch._matvec_factory`: XLA flat-stream or Pallas
  ELLPACK), M2/M6/M8 are row-wise dot modules writing ``[G]`` scalar
  registers, M3/M4/M7 the axpy family, M5 the Jacobi left-divide;
* **global controller** — an outer ``lax.while_loop`` that runs the
  program once per iteration and terminates each lane on the fly at its
  own ``rr_g ≤ τ_g`` (paper Challenge 1, batched): every state write —
  ``mem``, ``sregs``, **and** ``queues`` — is gated on the lane's
  ``active`` flag exactly like :func:`repro.core.batch._batched_body`,
  so a converged lane's *entire* VM state freezes mid-batch while the
  survivors keep iterating.

Two execution paths share the VM's semantics:

* **specialized** (the production default) — when the program is a
  concrete ``np.ndarray`` at Python time (it always is for the front
  doors: :func:`repro.core.batch.jpcg_solve_batched` and
  :class:`repro.serve.SolverEngine` both obtain it from
  :func:`repro.core.compile.canonical_program`), the program is unrolled
  at *trace time* into straight-line jnp ops with static buffer/queue
  indices: no ``lax.switch``, no per-word ``lax.cond``, no dynamic
  gather/scatter over monolithic state.  The ``[8, G, n]`` queue file is
  decomposed into per-queue ``[G, n]`` arrays and the ``[6, G, n]``
  memory file into per-buffer arrays, so only state the program actually
  touches enters the loop-carried dataflow and XLA fuses a whole
  iteration the way :func:`repro.core.phases.vsr_iteration` fuses — the
  JAX analogue of the FPGA paying dispatch once at synthesis.
  Executables are cached per
  ``(bucket, backend, scheme, maxiter/chunk, program bytes)``
  (:func:`repro.core.isa.program_token`): word-identical programs share
  one executable, a different schedule costs one specialization.
* **generic** (``specialize=False``, the fallback) — the program is a
  *traced operand* dispatched word-at-a-time by ``lax.switch``; one
  compiled executable (cached per bucket/backend/scheme, the key
  deliberately excludes the program) runs paper-policy, min-traffic,
  plain-CG, or any other program of the same padded length with **no
  retrace** — the analogue of not re-running synthesis/place/route per
  problem.  Prefer it when programs are generated at runtime faster
  than they can be specialized (schedule search, fuzzing).

``tests/test_compile.py`` asserts bit-level agreement of both paths with
the phase engine and the cache economics of each; the front doors are
:func:`repro.core.batch.jpcg_solve_batched` (``engine="vm"``) and
:class:`repro.serve.SolverEngine`.
"""
from __future__ import annotations

from typing import Any, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.batch import (Replacement, _cached, _matvec_factory,
                              _row_dot, _run_chunked, replace_residual)
from repro.core.dfloat import DF, hi, is_wide, select
from repro.core.compile import executable_key
from repro.core.isa import (BUF, CTRL_ALPHA, ITYPE_COMP, ITYPE_CTRL,
                            ITYPE_VCTRL, SREG)
from repro.core.metrics import (advance_status, finalize_status,
                                initial_status, tick_health)
from repro.core.precision import get_scheme

__all__ = ["BatchedVMState", "make_vm_runner", "make_vm_stepper",
           "vm_executable_stats", "vm_solve"]

_N_QUEUES = 8
_N_SREGS = 6
_N_BUFS = 6

#: COMP module id -> executor branch (0=spmv, 1=dot, 2=axpy, 3=div); the
#: VM's branch table is fixed, like the FPGA's module array.
_BRANCH_OF_MOD = (0, 1, 2, 2, 3, 1, 2, 1)


class BatchedVMState(NamedTuple):
    """Lane-batched VM state; every array's lane axis is G."""

    k: jax.Array         # global tick (int32 scalar)
    it: jax.Array        # int32[G] per-lane iteration counts
    status: jax.Array    # int32[G] exit codes (repro.core.metrics.STATUS_*)
    mem: jax.Array       # [6, G, n] HBM vector buffers (x r p ap M b)
    queues: jax.Array    # [8, G, n] inter-module streams
    sregs: jax.Array     # [6, G] scalar registers (α β rz rr pap rz')
    active: jax.Array    # bool[G] live-lane mask
    trace: jax.Array     # [G, maxiter] rr per iteration, or [G, 0]
    rep: Any = None      # Replacement under a wide scheme, else None


def _masked_trace(trace, k, keep, rr_new):
    """Record ``rr`` at column ``k`` for live lanes, or nothing at all
    when ``k`` is past the trace width.

    A with-trace state continued through :func:`make_vm_stepper` beyond
    its trace width drives ``k`` out of range.  The unguarded write only
    stayed a no-op because JAX silently *drops* out-of-bounds scatter
    updates (while the ``trace[:, k]`` gather feeding it clamps) —
    implicit semantics the solver must not lean on; the guard makes the
    out-of-range no-op explicit.
    """
    width = trace.shape[1]
    if not width:
        return trace
    safe_k = jnp.minimum(k, width - 1)
    ok = keep & (k < width)
    return trace.at[:, safe_k].set(jnp.where(ok, rr_new, trace[:, safe_k]))


# ------------------------------------------------------------ generic path
def _make_executor(matvec):
    """Per-instruction executor closed over the batched SpMV closure."""

    def exec_vctrl(w, st: BatchedVMState) -> BatchedVMState:
        buf, rd, wr, qa, qd = w[1], w[2], w[3], w[4], w[6]
        # rd: queue[qd] <- mem[buf] ; wr: mem[buf] <- queue[qa]
        q = jax.lax.cond(
            rd == 1,
            lambda: st.queues.at[qd].set(st.mem[buf]),
            lambda: st.queues)
        m = jax.lax.cond(
            wr == 1,
            lambda: st.mem.at[buf].set(st.queues[qa]),
            lambda: st.mem)
        return st._replace(mem=m, queues=q)

    def exec_comp(w, st: BatchedVMState) -> BatchedVMState:
        mod, neg, qa, qb, qd, sr = w[1], w[2], w[4], w[5], w[6], w[7]
        a = st.queues[qa]                       # [G, n]
        bq = st.queues[qb]
        s = st.sregs[sr]                        # [G]
        s = jnp.where(neg == 1, -s, s)

        def spmv():      # M1
            return st.queues.at[qd].set(matvec(a)), st.sregs

        def dot():       # M2 / M6 / M8 -> scalar register (row-wise)
            return st.queues, st.sregs.at[sr].set(_row_dot(a, bq))

        def axpy():      # M3 / M4 / M7: dst = a + s·b (per lane)
            return st.queues.at[qd].set(a + s[:, None] * bq), st.sregs

        def div():       # M5: dst = a / b  (Jacobi left-divide)
            return st.queues.at[qd].set(a / bq), st.sregs

        branch = jnp.array(_BRANCH_OF_MOD, jnp.int32)[mod]
        q, sregs = jax.lax.switch(branch, [spmv, dot, axpy, div])
        return st._replace(queues=q, sregs=sregs)

    def exec_ctrl(w, st: BatchedVMState) -> BatchedVMState:
        def alpha():     # α = rz / pap, per lane
            return st.sregs.at[SREG["alpha"]].set(
                st.sregs[SREG["rz"]] / st.sregs[SREG["pap"]])

        def beta():      # β = rz' / rz ; rz ← rz'
            s = st.sregs.at[SREG["beta"]].set(
                st.sregs[SREG["rz_new"]] / st.sregs[SREG["rz"]])
            return s.at[SREG["rz"]].set(st.sregs[SREG["rz_new"]])

        return st._replace(sregs=jax.lax.switch(w[1], [alpha, beta]))

    def exec_nop(w, st: BatchedVMState) -> BatchedVMState:
        return st

    def execute(w, st: BatchedVMState) -> BatchedVMState:
        return jax.lax.switch(
            w[0], [lambda: exec_vctrl(w, st), lambda: exec_comp(w, st),
                   lambda: exec_ctrl(w, st), lambda: exec_nop(w, st)])

    return execute


def vm_init(matvec, diag, b, x0, *, maxiter: int, with_trace: bool,
            tol, detect: bool = True) -> BatchedVMState:
    """Controller warm-up (paper Alg. 1 lines 1–5) — arithmetic identical
    to :func:`repro.core.batch._batched_init`, packed into VM buffers.

    Wide operands (:mod:`repro.core.dfloat`) keep ``mem`` as a tuple of
    per-buffer values: x has three fp32 words and the other buffers two,
    so one stacked array would give every buffer a third word (half as
    much again of memory and of every vector pass).  They carry no queue
    file: the specialized path derives every queue within a tick, and
    the generic VM, which would need wide queues, refuses the scheme."""
    G = b.shape[0]
    r = b - matvec(x0)
    z = r / diag
    rz = _row_dot(r, z)
    rr = _row_dot(r, r)
    if is_wide(b):
        sregs = DF.zeros((_N_SREGS, G))
        sregs = sregs.at[SREG["rz"]].set(rz).at[SREG["rr"]].set(rr)
        return BatchedVMState(
            k=jnp.zeros((), jnp.int32), it=jnp.zeros(G, jnp.int32),
            status=initial_status(rr.hi, tol, detect=detect),
            mem=(x0, r, z, DF.zeros(r.shape), diag, b), queues=None,
            sregs=sregs, active=rr.hi > tol,
            trace=jnp.zeros((G, maxiter if with_trace else 0), jnp.float32),
            rep=Replacement(rr.hi, jnp.zeros(G, jnp.int32)))
    vd = b.dtype
    mem = jnp.stack([x0, r, z, jnp.zeros_like(r), diag, b])  # x r p ap M b
    sregs = jnp.zeros((_N_SREGS, G), vd)
    sregs = sregs.at[SREG["rz"]].set(rz).at[SREG["rr"]].set(rr)
    return BatchedVMState(
        k=jnp.zeros((), jnp.int32), it=jnp.zeros(G, jnp.int32),
        status=initial_status(rr, tol, detect=detect), mem=mem,
        queues=jnp.zeros((_N_QUEUES,) + r.shape, vd), sregs=sregs,
        active=rr > tol,
        trace=jnp.zeros((G, maxiter if with_trace else 0), vd))


def _vm_body(program, matvec, tol, maxiter_vec=None, *, bound=None,
             write_trace=True, detect=True):
    """One VM tick = run the program once = one JPCG iteration per lane.

    Frozen (converged) lanes flow through the arithmetic — dead compute
    on a SIMD device — but ``mem``/``sregs``/``queues`` writes are gated
    on ``active``, mirroring the masking semantics of
    :func:`repro.core.batch._batched_body` bit for bit.  (Queues included:
    a frozen lane's streams must not drift, or continuing a state through
    the serving stepper / bucket growth becomes nondeterministic.)

    ``bound``/``write_trace`` mirror :func:`repro.core.batch._batched_body`:
    the tick self-gates so it can run inside an iteration chunk (the
    whole tick is a no-op once every lane converged or ``k`` reached
    ``bound``), and the chunked with-trace runner hoists the trace
    scatter out of the tick.

    ``detect`` reads the tick's *candidate* scalar registers (``pap`` /
    ``alpha`` / ``beta`` / ``rr`` — every canonical program writes them;
    a custom program that doesn't must run with ``detect=False``) through
    :func:`repro.core.metrics.tick_health`: a lane that trips it discards
    the whole tick — ``mem``/``queues``/``sregs`` untouched, ``it`` not
    advanced — and latches its breakdown ``status``.  Masking semantics
    stay word-for-word identical to :func:`repro.core.batch._batched_body`.
    """
    execute = _make_executor(matvec)

    def body(st: BatchedVMState) -> BatchedVMState:
        def step(pc, s):
            return execute(program[pc], s)

        nxt = jax.lax.fori_loop(0, program.shape[0], step, st)
        go = jnp.any(st.active)
        if bound is not None:
            go = go & (st.k < bound)
        keep = st.active & go
        rr_cand = nxt.sregs[SREG["rr"]]
        upd, bd_i, bd_n = tick_health(
            keep, nxt.sregs[SREG["pap"]], nxt.sregs[SREG["alpha"]],
            nxt.sregs[SREG["beta"]], rr_cand, detect=detect)
        mem = jnp.where(upd[None, :, None], nxt.mem, st.mem)
        queues = jnp.where(upd[None, :, None], nxt.queues, st.queues)
        sregs = jnp.where(upd[None, :], nxt.sregs, st.sregs)
        it = st.it + upd.astype(jnp.int32)
        rr = sregs[SREG["rr"]]
        if write_trace:
            trace = _masked_trace(st.trace, st.k, upd, rr_cand)
        else:
            trace = st.trace
        live = rr > tol
        if maxiter_vec is not None:
            live = live & (it < maxiter_vec)
        if detect:
            live = live & ~(bd_i | bd_n)
        status = advance_status(st.status, upd=upd, bd_indef=bd_i,
                                bd_nonf=bd_n, rr_new=rr_cand, tol=tol,
                                it=it, maxiter_vec=maxiter_vec)
        active = jnp.where(keep, live, st.active)
        return BatchedVMState(k=st.k + go.astype(jnp.int32), it=it,
                              status=status, mem=mem, queues=queues,
                              sregs=sregs, active=active, trace=trace)

    return body


# -------------------------------------------------------- specialized path
class _ProgramPlan(NamedTuple):
    """Trace-time analysis of a concrete program.

    ``carried_bufs`` / ``live_queues`` define the *loop-carried* state —
    everything else provably cannot influence (or be influenced by) the
    iteration and bypasses the ``lax.while_loop`` entirely:

    * a buffer the program neither loads nor stores (e.g. ``b`` after
      warm-up) is dead weight — it rides through from the initial state;
    * a queue whose first access within one program execution is a
      *write* is **phase-local**: the program re-derives it from memory
      every iteration, so carrying its value between iterations moves
      ``[G, n]`` data for nothing.  Only queues that are read before
      written (live-in) must be carried.  Compiled canonical programs
      have *zero* live-in queues — every consumed stream is loaded by a
      VecCtrl ``rd`` or produced by an earlier module in the same
      execution — so the steady-state carry is exactly the paper's
      loop-carried vectors plus scalars.
    """

    ops: Tuple[Tuple[int, ...], ...]   # decoded words (python ints)
    read_bufs: Tuple[int, ...]         # HBM buffers the program loads
    written_bufs: Tuple[int, ...]      # HBM buffers the program stores to
    carried_bufs: Tuple[int, ...]      # read ∪ written (the mem carry)
    live_queues: Tuple[int, ...]       # queues read before first write
    written_queues: Tuple[int, ...]    # queues written


def _analyze_program(program: np.ndarray) -> _ProgramPlan:
    """Decode a concrete program and compute the state it touches.

    Only touched buffers and *live-in* queues enter the specialized
    loop's carried dataflow (see :class:`_ProgramPlan`); the rest bypass
    the ``lax.while_loop`` entirely and are reattached from the initial
    state afterwards.
    """
    ops = tuple(tuple(int(v) for v in w)
                for w in np.asarray(program, np.int32))
    rb, wb, wq, live = set(), set(), set(), set()

    def read_queue(q):
        if q not in wq:                  # first access is a read: live-in
            live.add(q)

    for w in ops:
        if w[0] == ITYPE_VCTRL:
            # combined rd+wr words see pre-instruction state (snapshot
            # semantics, same as _run_specialized): account the queue
            # read before the queue write.
            if w[3]:                     # wr: queue[qa] -> mem[buf]
                read_queue(w[4])
                wb.add(w[1])
            if w[2]:                     # rd: mem[buf] -> queue[qd]
                rb.add(w[1])
                wq.add(w[6])
        elif w[0] == ITYPE_COMP:
            kind = _BRANCH_OF_MOD[w[1]]
            read_queue(w[4])             # qa
            if kind != 0:                # dot / axpy / div read qb too
                read_queue(w[5])
            if kind != 1:                # spmv / axpy / div write qd
                wq.add(w[6])
    return _ProgramPlan(ops=ops, read_bufs=tuple(sorted(rb)),
                        written_bufs=tuple(sorted(wb)),
                        carried_bufs=tuple(sorted(rb | wb)),
                        live_queues=tuple(sorted(live)),
                        written_queues=tuple(sorted(wq)))


def _run_specialized(plan: _ProgramPlan, matvec, mem: dict, queues: dict,
                     sregs):
    """Execute the program once, straight-line, with static indices.

    ``mem`` is a dict ``{buffer id: [G, n]}`` over the plan's carried
    buffers, ``queues`` a dict ``{queue id: [G, n]}`` over its live-in
    queues (phase-local queues materialize on first write).  The
    arithmetic is word-for-word the generic executor's — same ops, same
    order, same dtypes — only the dispatch is resolved at trace time, so
    results are bit-identical to the generic path (and hence to the
    phases oracle).  The device operations of each module kind carry a
    scope of its own (``vm_dot``, ``vm_axpy``, ``vm_div``, ``vm_ctrl``;
    M1's is the matvec's), which changes their metadata only.
    """
    mem = dict(mem)
    queues = dict(queues)
    for w in plan.ops:
        if w[0] == ITYPE_VCTRL:
            buf, rd, wr, qa, qd = w[1], w[2], w[3], w[4], w[6]
            src_m = mem[buf]             # pre-instruction snapshots: a
            src_q = queues.get(qa)       # combined rd+wr word sees old state
            if wr:
                mem[buf] = src_q
            if rd:
                queues[qd] = src_m
        elif w[0] == ITYPE_COMP:
            mod, neg, qa, qb, qd, sr = w[1], w[2], w[4], w[5], w[6], w[7]
            kind = _BRANCH_OF_MOD[mod]
            a = queues[qa]
            if kind == 0:                # M1: SpMV (scoped by the matvec)
                queues[qd] = matvec(a)
            elif kind == 1:              # M2/M6/M8: row-wise dot -> sreg
                with jax.named_scope("vm_dot"):
                    sregs = sregs.at[sr].set(_row_dot(a, queues[qb]))
            elif kind == 2:              # M3/M4/M7: dst = a ± s·b
                with jax.named_scope("vm_axpy"):
                    s = sregs[sr]
                    if neg:
                        s = -s
                    queues[qd] = a + s[:, None] * queues[qb]
            else:                        # M5: dst = a / b
                with jax.named_scope("vm_div"):
                    queues[qd] = a / queues[qb]
        elif w[0] == ITYPE_CTRL:
            with jax.named_scope("vm_ctrl"):
                if w[1] == CTRL_ALPHA:   # α = rz / pap
                    sregs = sregs.at[SREG["alpha"]].set(
                        sregs[SREG["rz"]] / sregs[SREG["pap"]])
                else:                    # β = rz'/rz ; rz ← rz'
                    new = sregs.at[SREG["beta"]].set(
                        sregs[SREG["rz_new"]] / sregs[SREG["rz"]])
                    sregs = new.at[SREG["rz"]].set(sregs[SREG["rz_new"]])
        # NOP words vanish at trace time
    return mem, queues, sregs


class _SpecCarry(NamedTuple):
    """Loop-carried state of the specialized path: per-buffer / per-queue
    arrays instead of the monolithic files, so XLA sees straight-line
    dataflow through exactly the state the program *proves* it needs —
    carried buffers and live-in queues only (:class:`_ProgramPlan`);
    dead buffers and phase-local queues never enter the loop."""

    k: jax.Array
    it: jax.Array
    status: jax.Array
    mem: Tuple[jax.Array, ...]       # carried buffers only, [G, n] each
    queues: Tuple[jax.Array, ...]    # live-in queues only, [G, n] each
    sregs: jax.Array
    active: jax.Array
    trace: jax.Array
    rep: Any = None


def _spec_carry_of(st: BatchedVMState, plan: _ProgramPlan) -> _SpecCarry:
    return _SpecCarry(
        k=st.k, it=st.it, status=st.status,
        mem=tuple(st.mem[i] for i in plan.carried_bufs),
        queues=tuple(st.queues[q] for q in plan.live_queues),
        sregs=st.sregs, active=st.active, trace=st.trace, rep=st.rep)


def _state_of_spec_carry(c: _SpecCarry, st0: BatchedVMState,
                         plan: _ProgramPlan) -> BatchedVMState:
    """Reassemble a full :class:`BatchedVMState`.

    State the loop did not carry passes through from ``st0``: buffers
    the program never touches, and — since the live-in analysis — every
    *phase-local* queue (written before read).  A phase-local queue's
    contents are an artifact of the last execution, re-derived from
    memory on the next; preserving the incoming value is the documented
    pass-through contract (asserted by the serving-engine tests).
    """
    mem = st0.mem
    if isinstance(mem, tuple):               # wide: one value a buffer
        done = dict(zip(plan.carried_bufs, c.mem))
        mem = tuple(done.get(i, v) for i, v in enumerate(mem))
    else:
        for i, v in zip(plan.carried_bufs, c.mem):
            mem = mem.at[i].set(v)
    queues = st0.queues
    for q, v in zip(plan.live_queues, c.queues):
        queues = queues.at[q].set(v)
    return BatchedVMState(k=c.k, it=c.it, status=c.status, mem=mem,
                          queues=queues, sregs=c.sregs, active=c.active,
                          trace=c.trace, rep=c.rep)


def _spec_body(plan: _ProgramPlan, matvec, tol, maxiter_vec=None, *,
               bound=None, write_trace=True, detect=True, b=None):
    """Specialized VM tick — identical masking semantics to
    :func:`_vm_body`, applied per carried buffer/queue; ``bound`` makes
    the tick self-gating for chunked execution (see
    :func:`repro.core.batch._batched_body`); ``detect`` classifies the
    same candidate scalar registers through the same
    :func:`repro.core.metrics.tick_health`, so the two VM paths stay
    guaranteed-identical with detection on or off.  ``b`` (a wide
    scheme's right-hand side) arms
    :func:`repro.core.batch.replace_residual` on the tick's x, r, rz and
    rr, as the phases engine does."""
    wb = frozenset(plan.written_bufs)
    wq = frozenset(plan.written_queues)

    def body(c: _SpecCarry) -> _SpecCarry:
        m_in = dict(zip(plan.carried_bufs, c.mem))
        q_in = dict(zip(plan.live_queues, c.queues))
        n_mem, n_q, n_sregs = _run_specialized(plan, matvec, m_in, q_in,
                                               c.sregs)
        go = jnp.any(c.active)
        if bound is not None:
            go = go & (c.k < bound)
        keep = c.active & go
        rr_cand = n_sregs[SREG["rr"]]
        upd, bd_i, bd_n = tick_health(
            keep, hi(n_sregs[SREG["pap"]]), hi(n_sregs[SREG["alpha"]]),
            hi(n_sregs[SREG["beta"]]), hi(rr_cand), detect=detect)
        rep = c.rep
        if b is not None:
            x, r, rz = n_mem[BUF["x"]], n_mem[BUF["r"]], n_sregs[SREG["rz"]]
            r, rz, rr_cand, rep = replace_residual(
                matvec, b, m_in[BUF["M"]], x, r, rz, rr_cand, rep, upd, tol)
            n_mem = {**n_mem, BUF["r"]: r}
            n_sregs = n_sregs.at[SREG["rz"]].set(rz).at[SREG["rr"]].set(
                rr_cand)
        rr_cand = hi(rr_cand)
        kv = upd[:, None]
        mem = tuple(select(kv, n_mem[i], old) if i in wb else old
                    for i, old in zip(plan.carried_bufs, c.mem))
        queues = tuple(jnp.where(kv, n_q[q], old) if q in wq else old
                       for q, old in zip(plan.live_queues, c.queues))
        sregs = select(upd[None, :], n_sregs, c.sregs)
        it = c.it + upd.astype(jnp.int32)
        rr = hi(sregs[SREG["rr"]])
        if write_trace:
            trace = _masked_trace(c.trace, c.k, upd, rr_cand)
        else:
            trace = c.trace
        live = rr > tol
        if maxiter_vec is not None:
            live = live & (it < maxiter_vec)
        if detect:
            live = live & ~(bd_i | bd_n)
        status = advance_status(c.status, upd=upd, bd_indef=bd_i,
                                bd_nonf=bd_n, rr_new=rr_cand, tol=tol,
                                it=it, maxiter_vec=maxiter_vec)
        active = jnp.where(keep, live, c.active)
        return _SpecCarry(k=c.k + go.astype(jnp.int32), it=it,
                          status=status, mem=mem, queues=queues,
                          sregs=sregs, active=active, trace=trace, rep=rep)

    return body


# ------------------------------------------------------------ executables
def make_vm_runner(*, backend, scheme, maxiter, with_trace, layout=None,
                   groups=None, block_rows=None, col_tile=None,
                   n_col_tiles=None, steps_per_sync: int = 8,
                   donate: bool = False, detect: bool = True,
                   interpret=False, mesh=None,
                   program: Optional[np.ndarray] = None):
    """Build the jitted solve-to-completion VM runner for one bucket.

    With ``program=None`` (generic path) returns
    ``run(program, mat, diag, b, x0, tol) -> BatchedVMState`` — the
    program is a runtime operand and callers cache this runner keyed on
    the *bucket*, never on the program or VSR policy.

    With a concrete ``program`` array the runner is *specialized*: the
    program is unrolled at trace time and baked into the executable, the
    signature drops the operand —
    ``run(mat, diag, b, x0, tol) -> BatchedVMState`` — and callers must
    key their cache on :func:`repro.core.isa.program_token` of the
    program as well.

    ``steps_per_sync`` = VM ticks per termination-predicate sync
    (bit-identical for any value — ticks self-gate; see
    :func:`repro.core.batch._run_chunked`); it and ``donate`` must join
    the caller's cache key (:func:`repro.core.compile.executable_key`).
    ``donate=True`` donates the ``b``/``x0`` operands into the warm-up —
    only safe when the caller constructs them fresh per call.
    ``detect`` arms breakdown detection (static — joins the caller's
    cache key); leftover ``RUNNING`` statuses finalize to ``MAXITER``
    before the state is returned.  ``mesh`` shards the operands' lane
    axis over a device mesh before the jitted call
    (:mod:`repro.core.shard`; the caller's cache key must include the
    mesh signature) — lanes are independent, so results stay
    bit-identical to the single-device path.
    """
    scheme = get_scheme(scheme)
    matvec_of = _matvec_factory(
        backend=backend, scheme=scheme, layout=layout, groups=groups,
        block_rows=block_rows, col_tile=col_tile,
        n_col_tiles=n_col_tiles, interpret=interpret)
    hoist_trace = with_trace and steps_per_sync > 1
    rr_of = lambda s: hi(s.sregs[SREG["rr"]])  # noqa: E731

    if program is None:
        if scheme.wide:
            raise ValueError(f"scheme {scheme.name!r} runs on the "
                             "specialized VM only (pass program=)")
        def run(program, mat, diag, b, x0, tol):
            matvec = matvec_of(mat)
            st = vm_init(matvec, diag, b, x0, maxiter=maxiter,
                         with_trace=with_trace, tol=tol, detect=detect)
            tick = _vm_body(program, matvec, tol, bound=maxiter,
                            write_trace=not hoist_trace, detect=detect)

            def cond(s):
                return (s.k < maxiter) & jnp.any(s.active)

            out = _run_chunked(cond, tick, st, steps=steps_per_sync,
                               with_trace=with_trace, maxiter=maxiter,
                               rr_of=rr_of)
            return out._replace(status=finalize_status(out.status))

        fn = jax.jit(run, donate_argnums=(3, 4) if donate else ())
        if mesh is None:
            return fn
        from repro.core.shard import place_lanes, place_replicated

        def run_sharded(program, mat, diag, b, x0, tol):
            return fn(place_replicated(mesh, program),
                      place_lanes(mesh, mat), place_lanes(mesh, diag),
                      place_lanes(mesh, b), place_lanes(mesh, x0),
                      place_lanes(mesh, tol))

        run_sharded._cache_size = fn._cache_size   # vm_executable_stats
        return run_sharded

    plan = _analyze_program(program)
    if scheme.wide and plan.live_queues:
        raise ValueError(f"scheme {scheme.name!r} carries no queue between "
                         "ticks; this program reads queues it has not "
                         f"written ({plan.live_queues})")

    def run_spec(mat, diag, b, x0, tol):
        matvec = matvec_of(mat)
        st0 = vm_init(matvec, diag, b, x0, maxiter=maxiter,
                      with_trace=with_trace, tol=tol, detect=detect)
        tick = _spec_body(plan, matvec, tol, bound=maxiter,
                          write_trace=not hoist_trace, detect=detect,
                          b=b if scheme.wide else None)

        def cond(c):
            return (c.k < maxiter) & jnp.any(c.active)

        c = _run_chunked(cond, tick, _spec_carry_of(st0, plan),
                         steps=steps_per_sync, with_trace=with_trace,
                         maxiter=maxiter, rr_of=rr_of)
        out = _state_of_spec_carry(c, st0, plan)
        return out._replace(status=finalize_status(out.status))

    fn_spec = jax.jit(run_spec, donate_argnums=(2, 3) if donate else ())
    if mesh is None:
        return fn_spec
    from repro.core.shard import place_lanes

    def run_spec_sharded(mat, diag, b, x0, tol):
        return fn_spec(place_lanes(mesh, mat), place_lanes(mesh, diag),
                       place_lanes(mesh, b), place_lanes(mesh, x0),
                       place_lanes(mesh, tol))

    run_spec_sharded._cache_size = fn_spec._cache_size
    return run_spec_sharded


def make_vm_stepper(*, backend, scheme, bucket, chunk, layout=None,
                    groups=None, index_bytes=None, block_rows=None,
                    col_tile=None, n_col_tiles=None,
                    steps_per_sync: int = 8, donate: bool = False,
                    detect: bool = True, interpret=False, mesh=None,
                    program: Optional[np.ndarray] = None):
    """Jitted bounded VM stepper for incremental serving (SolverEngine).

    Runs at most ``chunk`` program executions (= iterations) from a given
    state; per-lane budgets come in as ``maxiter_vec``.  Cached in the
    batch compile cache; ``bucket`` is the padded-operand dims tuple that
    keys the cache (row-ELL ``(n_pad, W)`` on XLA).

    * ``program=None`` — generic: cached per (backend, scheme, bucket,
      chunk), NOT per program, so every policy's program reuses one
      executable.  Returns
      ``step(program, mat, state, tol, maxiter_vec) -> state``.
    * concrete ``program`` — specialized: the program is baked in and the
      cache key gains its :func:`~repro.core.isa.program_token`, so
      word-identical programs share one executable and each distinct
      schedule costs one.  Returns
      ``step(mat, state, tol, maxiter_vec) -> state``.

    ``steps_per_sync`` ticks run per termination sync (capped at
    ``chunk``; bit-identical — each tick self-gates on the remaining
    budget, so ``k`` never overshoots ``chunk``).  ``donate=True``
    donates the *state* operand: the caller must not touch the passed
    state again (the serving engine's linear state hand-off; anything it
    retains across a step — harvested results — must be materialized
    first).  (No separate diag operand on either path — the
    preconditioner lives in ``mem[M]``.)

    ``mesh`` shards the lane axis over a device mesh
    (:mod:`repro.core.shard`): operands and state are re-placed with
    ``NamedSharding`` before every step (a no-op once they carry the
    target layout), and the mesh signature joins the cache key so the
    sharded stepper never collides with the single-device one.
    """
    scheme = get_scheme(scheme)
    inner = max(1, min(int(steps_per_sync), int(chunk)))
    key_kw = dict(backend=backend, scheme=scheme.name, bucket=bucket,
                  layout=layout, index_bytes=index_bytes, chunk=chunk,
                  steps_per_sync=inner, donate=donate, detect=detect,
                  interpret=interpret, mesh=mesh)

    def chunked(cond, tick, st):
        if inner <= 1:
            return jax.lax.while_loop(cond, tick, st)
        return jax.lax.while_loop(
            cond,
            lambda s: jax.lax.fori_loop(0, inner, lambda _, ss: tick(ss),
                                        s),
            st)

    if program is None:
        key = executable_key("vm_step", **key_kw)

        def make():
            matvec_of = _matvec_factory(
                backend=backend, scheme=scheme, layout=layout,
                groups=groups, block_rows=block_rows, col_tile=col_tile,
                n_col_tiles=n_col_tiles, interpret=interpret)

            def step(program, mat, state, tol, maxiter_vec):
                matvec = matvec_of(mat)
                start = state.k
                tick = _vm_body(program, matvec, tol, maxiter_vec,
                                bound=start + chunk, detect=detect)

                def cond(s):
                    return (s.k - start < chunk) & jnp.any(s.active)

                return chunked(cond, tick, state)

            fn = jax.jit(step, donate_argnums=(2,) if donate else ())
            if mesh is None:
                return fn
            from repro.core.shard import (place_lanes, place_replicated,
                                          place_vm_state)

            def step_sharded(program, mat, state, tol, maxiter_vec):
                return fn(place_replicated(mesh, program),
                          place_lanes(mesh, mat),
                          place_vm_state(mesh, state),
                          place_lanes(mesh, tol),
                          place_lanes(mesh, maxiter_vec))

            step_sharded._cache_size = fn._cache_size
            return step_sharded

        return _cached(key, make)

    prog = np.asarray(program, np.int32)
    key = executable_key("vm_step_spec", program=prog, **key_kw)

    def make_spec():
        matvec_of = _matvec_factory(
            backend=backend, scheme=scheme, layout=layout, groups=groups,
            block_rows=block_rows, col_tile=col_tile,
            n_col_tiles=n_col_tiles, interpret=interpret)
        plan = _analyze_program(prog)

        def step(mat, state, tol, maxiter_vec):
            matvec = matvec_of(mat)
            start = state.k
            tick = _spec_body(plan, matvec, tol, maxiter_vec,
                              bound=start + chunk, detect=detect)

            def cond(c):
                return (c.k - start < chunk) & jnp.any(c.active)

            c = chunked(cond, tick, _spec_carry_of(state, plan))
            return _state_of_spec_carry(c, state, plan)

        fn = jax.jit(step, donate_argnums=(1,) if donate else ())
        if mesh is None:
            return fn
        from repro.core.shard import place_lanes, place_vm_state

        def step_sharded(mat, state, tol, maxiter_vec):
            return fn(place_lanes(mesh, mat),
                      place_vm_state(mesh, state),
                      place_lanes(mesh, tol),
                      place_lanes(mesh, maxiter_vec))

        step_sharded._cache_size = fn._cache_size
        return step_sharded

    return _cached(key, make_spec)


def vm_executable_stats() -> dict:
    """VM executables in the batch compile cache + total traced shapes.

    ``specialized`` counts program-baked executables (cache keys
    ``vm_*_spec``, one per distinct program bytes per bucket);
    ``generic`` counts traced-operand executables (program excluded from
    the key).  ``traces`` counts jit cache entries across all of them:
    on the generic path, running a *different program* through an
    existing executable must not change it (the no-retrace acceptance
    check); only a new bucket shape, backend, scheme, or program *length*
    may.  On the specialized path new program bytes cost one entry by
    design.
    """
    from repro.core.batch import _CACHE
    fns, spec, gen = [], 0, 0
    for k, fn in _CACHE.items():
        if not (isinstance(k, tuple) and k and str(k[0]).startswith("vm_")):
            continue
        fns.append(fn)
        if str(k[0]).endswith("_spec"):
            spec += 1
        else:
            gen += 1
    return {"executables": len(fns), "specialized": spec, "generic": gen,
            "traces": int(sum(f._cache_size() for f in fns))}


# ---------------------------------------------------------------- public
def vm_solve(a, b=None, x0=None, *, program: np.ndarray, tol: float = 1e-12,
             maxiter: int = 20_000, scheme="mixed_v3",
             block_rows: int = 256, col_tile: int = 512,
             backend: str = "xla", specialize: bool = True,
             interpret: Optional[bool] = None) -> dict:
    """Solve Ax=b by executing ``program`` on the stream VM (batch of 1).

    Thin wrapper over :func:`repro.core.batch.jpcg_solve_batched` with
    ``engine="vm"`` — the single-system view of the one solver backend.
    ``specialize=False`` selects the generic traced-operand path.
    """
    from repro.core.batch import jpcg_solve_batched
    res = jpcg_solve_batched(
        [a], None if b is None else [b], None if x0 is None else [x0],
        tol=tol, maxiter=maxiter, scheme=scheme, backend=backend,
        engine="vm", program=program, specialize=specialize,
        block_rows=block_rows, col_tile=col_tile, interpret=interpret)[0]
    return {"x": res.x, "iterations": res.iterations, "rr": res.rr,
            "converged": res.converged}
