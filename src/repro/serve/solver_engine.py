"""Slot-based CG solver engine — continuous batching on the stream VM.

The solver twin of :class:`repro.serve.engine.DecodeEngine`, re-plumbed so
the batched stream VM (:mod:`repro.core.vm`) is the one execution backend:
every tick runs one jitted chunked VM step (≤ ``chunk_iters`` executions
of a stream-ISA program) over a fixed pool of problem slots.  Slots are
independent — each carries its own tolerance, iteration budget, and
``active`` flag, so a new system is admitted the moment an old one
converges, without disturbing in-flight lanes (their ``mem`` buffers are
frozen by the VM's masked updates).

Per-request policy and precision
--------------------------------
``submit(..., policy=, scheme=)`` overrides the engine-wide defaults per
request.  Requests are grouped into **pools** keyed by
``(scheme, policy)``; each pool owns ``batch_slots`` slots, its own
bucket, and its own compiled *program* — but programs are runtime
operands, so the compile-cache consequences are deliberately asymmetric:

* a new **scheme** (or a new bucket shape) costs one new VM executable —
  the cache key is :func:`repro.core.compile.executable_key`
  ``(kind, backend, scheme, bucket dims, chunk, steps_per_sync, donate,
  interpret)``;
* a new **policy** costs *nothing*: pools that differ only in policy
  share one jitted stepper and just pass a different ``int32[P, 8]``
  program (all programs are NOP-padded to one canonical length by
  :func:`repro.core.compile.canonical_program`).  This is the paper's
  one-bitstream-serves-any-schedule property, surfaced as an API
  guarantee; ``tests/test_compile.py`` asserts the trace counter stays
  flat across policies.

By default each pool steps through the *specialized* VM path — the
pool's program is unrolled into its stepper at trace time
(``SolverEngineConfig.specialize=False`` restores the traced-operand
stepper, under which policies are free but dispatch is word-at-a-time).
Under specialization a new policy costs one specialized stepper (cached
on the program's bytes via :func:`repro.core.isa.program_token`), and
word-identical programs still share one executable.

Admission (:meth:`SolverEngine.submit`) pads the problem's banked-ELL
arrays into a free slot of the pool's shared bucket shape and runs the
JPCG warm-up (r₀ = b − A·x₀, z₀ = M⁻¹r₀) for that lane only.  The bucket
is sized lazily from the pool's first admitted problem (dimensions
rounded up to power-of-two edges, :func:`repro.sparse.stacking.bucket_up`)
and grows — with one recompile — only when a larger problem arrives.

State-preservation invariants (regression-locked in ``tests``):

* **growth is lossless** — :meth:`_Pool._alloc` copies *all* in-flight
  VM state into the grown arrays: ``mem``, ``sregs``, **and** ``queues``
  (queues used to be silently reset to zeros, which would corrupt any
  program relying on live streams across executions — exactly the
  streams the compiler's live-stream preference creates);
* **frozen lanes are frozen** — the VM masks every state write
  (``mem``/``sregs``/``queues``) on the lane's ``active`` flag, so a
  converged slot's state is bit-stable no matter how many ticks the
  surviving lanes keep running.

Iteration economics (PR 7): each tick donates the pool's state into the
jitted stepper (``cfg.donate``, default on — so :meth:`_Pool.harvest`
materializes results to host before the buffers are consumed), runs
``steps_per_sync`` VM iterations per device round-trip inside the
chunk, and — when the occupied fraction drops below
``cfg.compact_fraction`` at a step boundary — repacks live lanes into
the smallest power-of-two lane bucket (:meth:`_Pool.maybe_compact`) so
converged lanes stop costing arithmetic.  Admission grows the lane
bucket back on demand.

>>> eng = SolverEngine(SolverEngineConfig(batch_slots=8, block_rows=8,
...                                       col_tile=128))
>>> rid = eng.submit(a, tol=1e-12)                      # paper policy
>>> rid2 = eng.submit(a2, policy="min_traffic")         # same executable
>>> done = eng.run_to_completion()                      # {rid: CGResult}
"""
from __future__ import annotations

import dataclasses
import time
from functools import partial
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.batch import (_as_csr, batch_cache_info,
                              batched_matvec_rowell, batched_matvec_sell,
                              batched_matvec_ellpack)
from repro.core.cg import CGResult
from repro.core.compile import canonical_program
from repro.core.isa import BUF, SREG
from repro.core.metrics import (Metrics, initial_status, is_breakdown,
                                is_breakdown_codes, record, span,
                                status_name, STATUS_MAXITER, STATUS_RUNNING)
from repro.core.precision import get_scheme
from repro.core.vm import BatchedVMState, make_vm_stepper
from repro.sparse.csr import CSRMatrix
from repro.sparse.ellpack import csr_to_ellpack
from repro.core.shard import mesh_shards, place_lanes, place_vm_state
from repro.sparse.stacking import (SELL_SLICE_ROWS, _sell_groups, bucket_up,
                                   choose_layout, csr_rowell, index_dtype,
                                   lane_bucket_up, pad_ellpack,
                                   sell_slice_widths, stack_sell)

__all__ = ["SolverEngineConfig", "SolverEngine"]


@dataclasses.dataclass(frozen=True)
class SolverEngineConfig:
    batch_slots: int = 8              # slots per (scheme, policy) pool
    scheme: str = "mixed_v3"          # default; per-request override
    policy: str = "paper"             # default VSR policy; per-request
    tol: float = 1e-12                # default; per-request override
    maxiter: int = 20_000             # default; per-request override
    chunk_iters: int = 64             # iterations per tick
    block_rows: int = 256
    col_tile: int = 512
    backend: str = "xla"              # "xla" | "pallas"
    layout: str = "auto"              # "auto" | "rowell" | "sell" (xla)
    #                                   "auto" | "ellpack" | "sell" (pallas);
    #                                   auto resolves per pool at first admit
    #                                   via the padding-ratio heuristic
    interpret: Optional[bool] = None  # pallas backend: None = auto
    specialize: bool = True           # program-specialized steppers
    steps_per_sync: int = 8           # VM ticks per termination sync
    donate: bool = True               # donate state into each step
    compact_fraction: float = 0.5     # repack lanes when live/lanes < this
    detect: bool = True               # in-loop breakdown detection
    escalate_fp64: bool = False       # retry a breakdown once at fp64
    escalate_scheme: str = "fp64"     # where escalation re-routes to
    mesh: Optional[object] = None     # jax.sharding.Mesh over the lane
    #                                   axis (repro.core.shard.lane_mesh);
    #                                   None = single-device pools


@partial(jax.jit, donate_argnums=0)
def _set_lane(arr, s, lane):
    """``arr.at[s].set(lane)`` in place: the slot-stacked operand is
    donated, so admission never holds two copies of it (at chip sizes
    one copy of a pallas pool is several GB)."""
    return arr.at[s].set(lane.astype(arr.dtype))


@partial(jax.jit, static_argnames=("scheme",))
def _lane_init_rowell(cols, vals, diag, b, x0, *, scheme):
    """JPCG warm-up for one lane (Alg. 1 lines 1–5, batch-of-one view)."""
    y = batched_matvec_rowell(cols[None], vals[None], x0[None],
                              scheme=scheme)[0]
    r = b - y
    z = r / diag
    return r, z, jnp.dot(r, z), jnp.dot(r, r)


@partial(jax.jit, static_argnames=("groups", "scheme"))
def _lane_init_sell(cols, vals, iperm, diag, b, x0, *, groups, scheme):
    """JPCG warm-up for one SELL-packed lane.  Used by both backends:
    the Pallas sell SpMV reduces through the same halving tree, so the
    XLA spelling is bit-identical and saves one kernel variant here."""
    y = batched_matvec_sell(cols[None], vals[None], iperm[None], x0[None],
                            groups=groups, scheme=scheme)[0]
    r = b - y
    z = r / diag
    return r, z, jnp.dot(r, z), jnp.dot(r, r)


@partial(jax.jit, static_argnames=("col_tile", "n_col_tiles", "scheme",
                                   "interpret"))
def _lane_init_ell(tc, v, lc, diag, b, x0, *, col_tile, n_col_tiles,
                   scheme, interpret):
    y = batched_matvec_ellpack(tc[None], v[None], lc[None], x0[None],
                               col_tile=col_tile, n_col_tiles=n_col_tiles,
                               scheme=scheme, interpret=interpret)[0]
    r = b - y
    z = r / diag
    return r, z, jnp.dot(r, z), jnp.dot(r, r)


class _Pool:
    """Slots + VM state for one (scheme, policy) request class."""

    def __init__(self, cfg: SolverEngineConfig, scheme, policy: str,
                 interpret: bool, metrics: Optional[Metrics] = None):
        self.cfg = cfg
        self.scheme = scheme
        self.policy = policy
        self.interpret = interpret
        self.metrics = metrics if metrics is not None else Metrics()
        self.program_np = np.asarray(canonical_program(policy), np.int32)
        self.program = jnp.asarray(self.program_np)
        self.mesh = cfg.mesh
        self.n_dev = mesh_shards(cfg.mesh)
        # Lane capacity: with a mesh the lane axis must stay divisible
        # by the shard count (NamedSharding), so the cap and every
        # resize round through lane_bucket_up (device-count-aware).
        self.capacity = (cfg.batch_slots if self.mesh is None
                         else lane_bucket_up(cfg.batch_slots,
                                             parts=self.n_dev))
        self.slots = self.capacity               # current lane capacity
        self.req_of_slot: list = [None] * self.slots   # request id or None
        self.n_of_slot = np.zeros(self.slots, np.int64)  # logical n per slot
        self.csr_of_slot: list = [None] * self.slots  # kept for sell rebuild
        self.bucket = None                       # per-backend dims tuple
        self.mat = None                          # slot-stacked arrays
        self.state: Optional[BatchedVMState] = None
        self.tol = None
        self.maxiter_vec = None
        # Matrix layout, resolved at first admit ("auto" applies the
        # padding-ratio heuristic to the first admitted system).
        self.layout = None if cfg.layout == "auto" else cfg.layout
        self.sell_widths = None                  # per-slice widths (sell)
        self.groups = None                       # static (rows, w) runs

    # ------------------------------------------------------------ sizing
    def _dims_of(self, m):
        """Pallas bucket signature: (row blocks, slabs, ell, col tiles).

        The XLA backend's row-ELL dims — ``(padded rows, row width)`` —
        come straight from the CSR in :meth:`admit`.
        """
        return (m.n_row_blocks, m.n_slabs, m.ell, m.n_col_tiles)

    def _lane_round(self, want: int) -> int:
        """Next lane-bucket edge — shard-divisible under a mesh."""
        return (bucket_up(want) if self.mesh is None
                else lane_bucket_up(want, parts=self.n_dev))

    def _n_pad(self, dims):
        if self.layout == "sell" or self.cfg.backend == "xla":
            return dims[0]
        return dims[0] * self.cfg.block_rows

    def _alloc(self, dims):
        """(Re)allocate the slot-stacked arrays for bucket ``dims`` at the
        current lane capacity ``self.slots``, copying any in-flight lanes.

        Serves three resize paths with one copy-and-pad: first admission,
        bucket growth (a larger problem arrives), and lane growth
        (admission after converged-lane compaction shrank the pool).

        Matrix operands per layout: row-ELL grows in place (the old
        slot-major region stays valid at any padded size because pad
        columns are row-own ids); sliced-ELL *rebuilds* every lane from
        the retained per-slot CSRs — the shared slice widths re-shuffle
        the flat slot offsets, so an in-place copy has no meaning.  VM
        *state* is layout-independent (original row order) and is always
        copied forward.
        """
        S = self.slots
        vd = self.scheme.vector_dtype
        md = self.scheme.matrix_dtype
        n_pad = self._n_pad(dims)
        old_mat, old_state = self.mat, self.state
        if len(self.req_of_slot) < S:
            pad_n = S - len(self.req_of_slot)
            self.req_of_slot += [None] * pad_n
            self.csr_of_slot += [None] * pad_n
            self.n_of_slot = np.pad(self.n_of_slot, (0, pad_n))

        if self.layout == "sell":
            # Full rebuild at the pool's shared geometry; empty slots get
            # a zero-nnz placeholder (self-gathering pad entries only).
            empty = CSRMatrix(np.zeros(2, np.int64), np.zeros(0, np.int32),
                              np.zeros(0, np.float64), (1, 1))
            stacked = stack_sell(
                [c if c is not None else empty for c in self.csr_of_slot],
                n_pad=n_pad, widths=self.sell_widths, scheme=self.scheme)
            self.groups = stacked.groups
            mat = (jnp.asarray(stacked.cols), jnp.asarray(stacked.vals),
                   jnp.asarray(stacked.iperm))
        elif self.cfg.backend == "xla":
            N, W = dims
            idt = index_dtype(N)
            # padding entries are (col i, val 0) for row i: self-gather,
            # so no lane can be poisoned through another row's x entry
            cols = jnp.broadcast_to(jnp.arange(N, dtype=idt), (S, W, N))
            mat = (cols, jnp.zeros((S, W, N), md))
        else:
            B, T, L, _ = dims
            R = self.cfg.block_rows
            mat = (jnp.zeros((S, B, T), jnp.int32),
                   jnp.zeros((S, B, T, L, R), md),
                   jnp.zeros((S, B, T, L, R), jnp.int32))
        mem = jnp.zeros((6, S, n_pad), vd)
        mem = mem.at[BUF["M"]].set(1.0)          # unit diag on empty rows
        state = BatchedVMState(
            k=jnp.zeros((), jnp.int32), it=jnp.zeros(S, jnp.int32),
            status=jnp.zeros(S, jnp.int32),
            mem=mem, queues=jnp.zeros((8, S, n_pad), vd),
            sregs=jnp.zeros((6, S), vd), active=jnp.zeros(S, bool),
            trace=jnp.zeros((S, 0), vd))
        tol = jnp.full(S, self.cfg.tol, vd)
        maxiter_vec = jnp.zeros(S, jnp.int32)

        if old_mat is not None:
            # Growing bucket and/or lane count: copy every old lane into
            # the new arrays — mem, sregs AND queues (live streams must
            # survive growth; padded tails stay zero, which is what a
            # wider VM would hold for rows that never existed).  New
            # lanes keep the fresh-alloc empty-lane state (unit diag).
            def grow(new, old):
                pads = [(0, n - o) for n, o in zip(new.shape, old.shape)]
                return jnp.pad(old, pads)
            if self.layout == "sell":
                pass            # mat fully rebuilt from the slot CSRs
            elif self.cfg.backend == "xla":
                # old slot-major [S0, W0, N0] region is valid verbatim;
                # .set also casts int16 cols up if N crossed 2^15
                mat = tuple(
                    new.at[tuple(slice(0, d) for d in old.shape)]
                    .set(old.astype(new.dtype))
                    for new, old in zip(mat, old_mat))
            else:
                mat = tuple(grow(n, o) for n, o in zip(mat, old_mat))
            S_old = old_state.mem.shape[1]
            old_n = old_state.mem.shape[-1]
            mem = mem.at[:, :S_old, :old_n].set(old_state.mem)
            queues = state.queues.at[:, :S_old, :old_n].set(
                old_state.queues)
            state = state._replace(
                k=old_state.k, it=grow(state.it, old_state.it), mem=mem,
                queues=queues, sregs=grow(state.sregs, old_state.sregs),
                active=grow(state.active, old_state.active),
                status=grow(state.status, old_state.status))
            tol = tol.at[:S_old].set(self.tol)
            maxiter_vec = maxiter_vec.at[:S_old].set(self.maxiter_vec)
            self.metrics.bump("growths")
        self.bucket = dims
        self.mat = mat
        self.state = state
        self.tol = tol
        self.maxiter_vec = maxiter_vec

    # ---------------------------------------------------------- admission
    def admit(self, a, b, x0, tol, maxiter) -> int:
        """Place one system into a free slot; returns the slot index."""
        with span("engine.admit.pack"):
            s, a = self._pack_lane(a)
        with span("engine.admit.warm"):
            self._warm_lane(s, a, b, x0, tol, maxiter)
        return s

    def _pack_lane(self, a):
        """Pack system ``a`` into a free slot's matrix operand, growing
        or rebuilding the pool where its geometry asks for it; returns
        the slot and ``a`` as CSR."""
        free = [s for s, r in enumerate(self.req_of_slot) if r is None]
        if not free and self.slots < self.capacity:
            # Compaction shrank the pool; grow lanes back for this admit.
            self.slots = min(self.capacity, self._lane_round(self.slots + 1))
            self._alloc(self.bucket)
            free = [s for s, r in enumerate(self.req_of_slot) if r is None]
        if not free:
            raise RuntimeError(
                f"no free solver slots in pool "
                f"(scheme={self.scheme.name}, policy={self.policy})")
        s = free[0]
        cfg = self.cfg
        a = _as_csr(a)
        if self.layout is None:
            self.layout = choose_layout(
                [a], default="rowell" if cfg.backend == "xla" else "ellpack")
        if self.layout == "sell":
            n_pad = bucket_up(a.shape[0])
            if self.bucket is not None:
                n_pad = max(n_pad, self.bucket[0])
            stored = [c for c in self.csr_of_slot if c is not None]
            wnew = sell_slice_widths(stored + [a], n_pad=n_pad)
            if self.sell_widths is not None:
                # n_pad growth appends zero-nnz rows, which a global sort
                # sends to the tail: old slice widths stay valid for the
                # leading slices, so the merge is a zero-padded max —
                # widths only ever grow (bucket-signature stability).
                old = self.sell_widths + (0,) * (len(wnew) -
                                                 len(self.sell_widths))
                wnew = tuple(max(o, w) for o, w in zip(old, wnew))
            self.csr_of_slot[s] = a
            if (self.bucket is None or n_pad != self.bucket[0]
                    or wnew != self.sell_widths):
                self.sell_widths = wnew
                groups = _sell_groups(wnew, n_pad=n_pad,
                                      slice_rows=max(1, min(SELL_SLICE_ROWS,
                                                            n_pad)))
                self._alloc((n_pad,) + tuple(
                    d for rw in groups for d in rw))
            else:
                st1 = stack_sell([a], n_pad=n_pad, widths=self.sell_widths,
                                 scheme=self.scheme)
                lanes = (st1.cols[0], st1.vals[0], st1.iperm[0])
                self.mat = tuple(_set_lane(arr, s, jnp.asarray(lane))
                                 for arr, lane in zip(self.mat, lanes))
        else:
            if cfg.backend == "xla":
                cols_l, vals_l = csr_rowell(a)
                dims = (bucket_up(a.shape[0]), bucket_up(cols_l.shape[1]))
            else:
                m = csr_to_ellpack(a, block_rows=cfg.block_rows,
                                   col_tile=cfg.col_tile)
                dims = tuple(bucket_up(d) for d in self._dims_of(m))
            if self.bucket is None or any(d > o for d, o in
                                          zip(dims, self.bucket)):
                grown = dims if self.bucket is None else tuple(
                    max(d, o) for d, o in zip(dims, self.bucket))
                self._alloc(grown)
            if cfg.backend == "xla":
                # slot-major lane slab over the whole bucket: self-gather
                # template, then the real entries transposed in
                N, W = self.bucket
                n, w_a = cols_l.shape
                idt = index_dtype(N)
                lane_cols = np.broadcast_to(np.arange(N, dtype=idt),
                                            (W, N)).copy()
                lane_cols[:w_a, :n] = cols_l.T
                lane_vals = np.zeros((W, N), self.scheme.matrix_dtype)
                lane_vals[:w_a, :n] = vals_l.T
                lanes = (lane_cols, lane_vals)
            else:
                B, T, L, _ = self.bucket
                m = pad_ellpack(m, n_row_blocks=B, n_slabs=T, ell=L)
                lanes = (m.tile_cols, m.vals, m.local_cols)
            self.csr_of_slot[s] = a
            self.mat = tuple(_set_lane(arr, s, jnp.asarray(lane))
                             for arr, lane in zip(self.mat, lanes))
        return s, a

    def _warm_lane(self, s, a, b, x0, tol, maxiter) -> None:
        """Put the lane's vectors on the device, run its JPCG warm-up
        and write its VM state."""
        cfg = self.cfg
        vd = self.scheme.vector_dtype
        n = a.shape[0]
        n_pad = self.state.mem.shape[-1]
        d = np.ones(n_pad)
        d[:n] = a.diagonal()
        bb = np.zeros(n_pad)
        bb[:n] = np.ones(n) if b is None else np.asarray(b)
        xx = np.zeros(n_pad)
        if x0 is not None:
            xx[:n] = np.asarray(x0)
        diag_l = jnp.asarray(d, vd)
        b_l = jnp.asarray(bb, vd)
        x0_l = jnp.asarray(xx, vd)

        if self.layout == "sell":
            lc, lv, lip = (arr[s] for arr in self.mat)
            r, z, rz, rr = _lane_init_sell(
                lc, lv, lip, diag_l, b_l, x0_l, groups=self.groups,
                scheme=self.scheme)
        elif cfg.backend == "xla":
            gc, v = (arr[s] for arr in self.mat)
            r, z, rz, rr = _lane_init_rowell(
                gc, v, diag_l, b_l, x0_l, scheme=self.scheme)
        else:
            tc, v, lc = (arr[s] for arr in self.mat)
            r, z, rz, rr = _lane_init_ell(
                tc, v, lc, diag_l, b_l, x0_l, col_tile=cfg.col_tile,
                n_col_tiles=self.bucket[-1], scheme=self.scheme,
                interpret=self.interpret)

        st = self.state
        lane_mem = jnp.stack([x0_l, r, z, jnp.zeros_like(r), diag_l, b_l])
        req_tol = jnp.asarray(cfg.tol if tol is None else tol, vd)
        sregs = st.sregs.at[:, s].set(0.0)
        sregs = sregs.at[SREG["rz"], s].set(rz)
        sregs = sregs.at[SREG["rr"], s].set(rr)
        self.state = st._replace(
            it=st.it.at[s].set(0), mem=st.mem.at[:, s].set(lane_mem),
            queues=st.queues.at[:, s].set(0.0), sregs=sregs,
            active=st.active.at[s].set(rr > req_tol),
            status=st.status.at[s].set(
                initial_status(rr, req_tol, detect=cfg.detect)))
        self.tol = self.tol.at[s].set(req_tol)
        self.maxiter_vec = self.maxiter_vec.at[s].set(
            cfg.maxiter if maxiter is None else maxiter)
        self.n_of_slot[s] = n
        self.metrics.bump("admits")
        self.metrics.bump("spmv_calls")          # the warm-up r0 = b - A·x0
        self.metrics.bump("bytes_streamed_est", self._lane_stream_bytes())

    def _lane_stream_bytes(self) -> int:
        """At-rest nonzero stream per lane per SpMV: packed values +
        column indices, padding included — i.e.
        ``scheme.nonzero_stream_bytes(index_bytes) × padding_ratio × nnz``
        computed directly from the slot-stacked arrays."""
        ellpack = self.cfg.backend == "pallas" and self.layout != "sell"
        if ellpack:
            nb = self.mat[1].nbytes + self.mat[2].nbytes
        else:
            nb = self.mat[0].nbytes + self.mat[1].nbytes
        return int(nb) // self.slots

    # -------------------------------------------------------------- tick
    @property
    def any_active(self) -> bool:
        return self.state is not None and bool(self.state.active.any())

    def step(self) -> None:
        cfg = self.cfg
        ellpack = cfg.backend == "pallas" and self.layout != "sell"
        index_bytes = int(self.mat[2 if ellpack else 0].dtype.itemsize)
        stepper_kw = dict(
            backend=cfg.backend, scheme=self.scheme, bucket=self.bucket,
            chunk=cfg.chunk_iters, layout=self.layout, groups=self.groups,
            index_bytes=index_bytes, block_rows=cfg.block_rows,
            col_tile=cfg.col_tile,
            n_col_tiles=self.bucket[-1] if ellpack else None,
            steps_per_sync=cfg.steps_per_sync, donate=cfg.donate,
            detect=cfg.detect, interpret=self.interpret, mesh=self.mesh)
        # Materialize the pre-step counters to host before the call —
        # with cfg.donate the state operand is consumed by the stepper.
        with span("engine.step.pull"):
            it0 = np.asarray(self.state.it)
            st0 = np.asarray(self.state.status)
        with span("engine.step.launch"):
            if cfg.specialize:
                stepper = make_vm_stepper(program=self.program_np,
                                          **stepper_kw)
                self.state = stepper(self.mat, self.state, self.tol,
                                     self.maxiter_vec)
            else:
                stepper = make_vm_stepper(**stepper_kw)
                self.state = stepper(self.program, self.mat, self.state,
                                     self.tol, self.maxiter_vec)
        with span("engine.step.wait"):
            it1 = np.asarray(self.state.it)
            st1 = np.asarray(self.state.status)
        # Accounting: committed iterations plus one discarded program
        # execution per lane that broke down during this step (its tick
        # ran the SpMV before the writes were thrown away).  Frozen
        # lanes' SIMD dead compute is deliberately NOT counted — it
        # streams nothing on the modeled architecture.
        it_delta = int((it1 - it0).sum())
        broke = int((is_breakdown_codes(st1)
                     & ~is_breakdown_codes(st0)).sum())
        m = self.metrics
        m.bump("chunks")
        m.bump("iterations", it_delta)
        m.bump("spmv_calls", it_delta + broke)
        m.bump("bytes_streamed_est",
               (it_delta + broke) * self._lane_stream_bytes())

    def harvest(self) -> Dict[int, CGResult]:
        if self.state is None:
            return {}
        done: Dict[int, CGResult] = {}
        active = np.asarray(self.state.active)
        its = np.asarray(self.state.it)
        statuses = np.asarray(self.state.status)
        rrs = np.asarray(self.state.sregs[SREG["rr"]])
        tols = np.asarray(self.tol)
        for s, rid in enumerate(self.req_of_slot):
            if rid is None or active[s]:
                continue
            n = int(self.n_of_slot[s])
            # Materialize to host: with cfg.donate the pool's device state
            # is consumed by the next step(), which would invalidate any
            # device view we handed out here.
            x = np.asarray(self.state.mem[BUF["x"], s, :n])
            # An inactive lane still RUNNING is the detection-off
            # non-finite-at-admit corner (it deactivated without ever
            # ticking); it wears the budget-exhausted face.
            code = int(statuses[s])
            if code == STATUS_RUNNING:
                code = STATUS_MAXITER
            done[rid] = CGResult(
                x=x, iterations=int(its[s]),
                rr=float(rrs[s]), converged=bool(rrs[s] <= tols[s]),
                residual_trace=None, scheme=self.scheme.name,
                method=f"vm_engine[{self.policy}]",
                status=status_name(code))
            self.req_of_slot[s] = None
            # release the CSR: a departed lane must not keep inflating
            # future sell width merges (widths stay monotone regardless)
            self.csr_of_slot[s] = None
            self.metrics.bump("harvests")
        return done

    # --------------------------------------------------------- compaction
    def maybe_compact(self) -> bool:
        """Repack live lanes into a smaller lane bucket when most slots
        sit idle.  Runs only at step boundaries (after harvest), when the
        occupied fraction drops strictly below ``cfg.compact_fraction``
        and the occupied count fits a smaller power-of-two lane bucket.
        Every VM op is lane-independent, so repacking is bitwise-neutral
        per lane; it trades one retrace (new lane count) for every
        subsequent tick costing arithmetic proportional to live lanes.
        Returns True if the pool was repacked.

        Under a lane mesh compaction is **device-local**: slot ``s``
        lives on shard ``s // (S/D)``, and live lanes are repacked
        within their own shard only — migrating a live lane would move
        its in-flight VM state across devices mid-solve.  The per-shard
        lane bucket is sized by the fullest shard, so the compacted
        lane count stays shard-divisible."""
        if self.state is None:
            return False
        S = self.slots
        occ = [s for s, r in enumerate(self.req_of_slot) if r is not None]
        live = len(occ)
        if live == 0:
            return False
        D = self.n_dev
        if D <= 1:
            target = bucket_up(live)
            if target >= S or live / S >= self.cfg.compact_fraction:
                return False
            sel = np.asarray(
                occ[:target] +
                [s for s in range(S) if s not in occ][: target - live],
                np.int64)
        else:
            per = S // D
            by_shard = [[s for s in occ if s // per == d] for d in range(D)]
            t_per = bucket_up(max(len(o) for o in by_shard))
            target = t_per * D
            if target >= S or live / S >= self.cfg.compact_fraction:
                return False
            sel_l: list = []
            for d, o in enumerate(by_shard):
                base = d * per
                free = [s for s in range(base, base + per)
                        if self.req_of_slot[s] is None]
                sel_l += (o + free)[:t_per]
            sel = np.asarray(sel_l, np.int64)
        with span("engine.compact"):
            self._repack(sel, target)
        return True

    def _repack(self, sel: np.ndarray, target: int) -> None:
        """Keep the lanes ``sel`` (live ones and free ones to fill the
        ``target`` lane count), in that order."""
        # The gathers come back replicated under a mesh: lay the lanes out
        # over it again, or every chip would hold the whole pool.
        sel_j = jnp.asarray(sel)
        self.mat = place_lanes(self.mesh,
                               tuple(arr[sel_j] for arr in self.mat))
        st = self.state
        self.state = place_vm_state(self.mesh, st._replace(
            it=st.it[sel_j], status=st.status[sel_j], mem=st.mem[:, sel_j],
            queues=st.queues[:, sel_j], sregs=st.sregs[:, sel_j],
            active=st.active[sel_j], trace=st.trace[sel_j]))
        self.tol = place_lanes(self.mesh, self.tol[sel_j])
        self.maxiter_vec = place_lanes(self.mesh, self.maxiter_vec[sel_j])
        self.req_of_slot = [self.req_of_slot[s] for s in sel]
        self.csr_of_slot = [self.csr_of_slot[s] for s in sel]
        self.n_of_slot = self.n_of_slot[sel]
        self.slots = target
        self.metrics.bump("compactions")


class SolverEngine:
    """Admit SPD systems into batch slots; solve them on the stream VM."""

    def __init__(self, cfg: SolverEngineConfig):
        self.cfg = cfg
        if cfg.interpret is None:
            from repro.kernels.ops import default_interpret
            self.interpret = default_interpret()
        else:
            self.interpret = cfg.interpret
        self._pools: Dict[Tuple[str, str], _Pool] = {}
        self._next_id = 0
        self.results: Dict[int, CGResult] = {}
        self._metrics = Metrics()
        # Request meta for the escalation policy: rid -> (a, b, x0, tol,
        # maxiter, policy).  Only populated when cfg.escalate_fp64 is on
        # (retaining every operand would defeat slot recycling otherwise).
        self._meta: Dict[int, tuple] = {}
        self._retried: set = set()
        # rid -> perf_counter_ns of its first admission, while spans are
        # recorded (the start of its ``engine.request`` record)
        self._admitted_ns: Dict[int, int] = {}

    def _pool(self, scheme: Optional[str], policy: Optional[str]) -> _Pool:
        scheme = get_scheme(self.cfg.scheme if scheme is None else scheme)
        if scheme.wide:
            raise ValueError(f"scheme {scheme.name!r} replaces residuals in "
                             "jpcg_solve_batched; the engine has no pool "
                             "for it")
        policy = self.cfg.policy if policy is None else policy
        key = (scheme.name, policy)
        if key not in self._pools:
            self._pools[key] = _Pool(self.cfg, scheme, policy,
                                     self.interpret, self._metrics)
        return self._pools[key]

    def metrics(self) -> dict:
        """Engine observability snapshot — a plain dict (json-safe).

        Counters: ``admits`` / ``harvests`` / ``escalations`` (request
        lifecycle), ``chunks`` / ``iterations`` / ``spmv_calls`` /
        ``bytes_streamed_est`` (work executed; bytes = SpMV events × the
        per-lane at-rest nonzero stream, padding included), ``growths`` /
        ``compactions`` (pool geometry events); ``exit_status`` is the
        histogram of *recorded* request exits (escalated-and-retried
        requests count once, at their final exit); ``pools`` reports
        per-(scheme, policy) slot occupancy; ``executable_cache`` is
        :func:`repro.core.batch.batch_cache_info`.
        """
        pools = {
            f"{sch}/{pol}": {
                "slots": p.slots,
                "shards": p.n_dev,
                "occupied": sum(r is not None for r in p.req_of_slot),
                "active": (int(p.state.active.sum())
                           if p.state is not None else 0),
            }
            for (sch, pol), p in self._pools.items()}
        return self._metrics.snapshot(extra={
            "pools": pools, "executable_cache": batch_cache_info()})

    # ------------------------------------------------------------ public
    def free_slots(self, pool: Optional[Tuple[Optional[str],
                                              Optional[str]]] = None) -> int:
        """Free solver slots across the whole engine.

        With ``pool=None`` (default) sums the free slots of **every**
        instantiated (scheme, policy) pool — per-request ``scheme=``/
        ``policy=`` overrides create pools lazily, and admission control
        steering on this number must see all of them.  (It used to count
        only the default pool, so callers saw phantom fullness — slots
        free in override pools — and phantom capacity — a full default
        pool reported while overrides were also full.)  Before any pool
        exists it reports ``cfg.batch_slots``, the capacity the first
        submit will materialize.

        ``pool=(scheme, policy)`` restores the single-pool view (``None``
        components fall back to the engine defaults); an uninstantiated
        pool reports its full capacity.
        """
        cap0 = (self.cfg.batch_slots if self.cfg.mesh is None
                else lane_bucket_up(self.cfg.batch_slots,
                                    parts=mesh_shards(self.cfg.mesh)))

        def pool_free(p: Optional[_Pool]) -> int:
            if p is None:
                return cap0
            # Capacity view: lanes a compacted pool currently materializes
            # is an implementation detail — admission grows them back, so
            # free capacity is configured slots minus occupied ones.
            return p.capacity - sum(
                r is not None for r in p.req_of_slot)

        if pool is not None:
            scheme, policy = pool
            key = (get_scheme(self.cfg.scheme if scheme is None
                              else scheme).name,
                   self.cfg.policy if policy is None else policy)
            return pool_free(self._pools.get(key))
        if not self._pools:
            return self.cfg.batch_slots
        return sum(pool_free(p) for p in self._pools.values())

    @property
    def active_count(self) -> int:
        return sum(int(p.state.active.sum()) for p in self._pools.values()
                   if p.state is not None)

    def submit(self, a, b=None, x0=None, *, tol: Optional[float] = None,
               maxiter: Optional[int] = None, policy: Optional[str] = None,
               scheme: Optional[str] = None) -> int:
        """Admit one SPD system; returns the request id.

        ``policy``/``scheme`` override the engine defaults per request and
        route the system to the matching (scheme, policy) pool — see the
        module docstring for what each override costs in executables.

        With ``cfg.escalate_fp64`` the request's operands are retained so
        a breakdown exit can be retried once in the
        ``cfg.escalate_scheme`` pool (the result then carries
        ``retried=True``).
        """
        rid = self._next_id
        with span("engine.submit", rid=rid):
            self._harvest()    # a lane done since the last tick frees its slot
            self._admit(self._pool(scheme, policy), rid, a, b, x0, tol,
                        maxiter)
        self._next_id += 1
        if self.cfg.escalate_fp64:
            self._meta[rid] = (a, b, x0, tol, maxiter,
                               self.cfg.policy if policy is None else policy)
        return rid

    def _admit(self, pool: _Pool, rid: int, a, b, x0, tol, maxiter) -> None:
        with span("engine.admit", rid=rid) as sp:
            s = pool.admit(a, b, x0, tol, maxiter)
        pool.req_of_slot[s] = rid
        if sp is not None:
            self._admitted_ns.setdefault(rid, sp.start_ns)

    def step(self) -> Dict[int, CGResult]:
        """One chunked tick (≤ ``chunk_iters`` iterations for every live
        lane in every pool); harvests and frees slots that finished,
        returning ``{request_id: CGResult}``."""
        with span("engine.step"):
            for pool in self._pools.values():
                if pool.any_active:
                    pool.step()
            done = self._harvest()
            for pool in self._pools.values():
                pool.maybe_compact()
        return done

    def _harvest(self) -> Dict[int, CGResult]:
        with span("engine.harvest"):
            raw: Dict[int, CGResult] = {}
            for pool in self._pools.values():
                raw.update(pool.harvest())
            done: Dict[int, CGResult] = {}
            for rid, res in raw.items():
                if self._should_escalate(rid, res):
                    # One retry at the escalation scheme: re-admit the
                    # retained operands into the target pool under the
                    # SAME request id — the caller sees one request, one
                    # (final) result, with retried=True.
                    a, b, x0, tol, maxiter, policy = self._meta[rid]
                    self._admit(self._pool(self.cfg.escalate_scheme, policy),
                                rid, a, b, x0, tol, maxiter)
                    self._retried.add(rid)
                    self._metrics.bump("escalations")
                    continue
                res.retried = rid in self._retried
                self._metrics.record_exit(res.status)
                self._meta.pop(rid, None)
                self._retried.discard(rid)
                done[rid] = res
            self.results.update(done)
        if done and self._admitted_ns:
            now = time.perf_counter_ns()
            for rid in done:
                start = self._admitted_ns.pop(rid, None)
                if start is not None:
                    record("engine.request", start, now, rid)
        return done

    def _should_escalate(self, rid: int, res: CGResult) -> bool:
        if not (self.cfg.escalate_fp64 and is_breakdown(res.status)):
            return False
        if rid in self._retried or rid not in self._meta:
            return False
        target = get_scheme(self.cfg.escalate_scheme)
        if res.scheme == target.name:
            return False       # already ran at the escalation scheme
        if (target.vector_dtype == jnp.float64
                and not jax.config.read("jax_enable_x64")):
            return False       # fp64 retry impossible without x64
        return True

    def run_to_completion(self, max_ticks: int = 10_000) -> Dict[int, CGResult]:
        """Tick until every admitted system finished; returns all results
        harvested during the call.  Raises if ``max_ticks`` elapses with
        lanes still live (truncation must be observable, not a silently
        missing request id)."""
        out: Dict[int, CGResult] = {}
        out.update(self._harvest())
        ticks = 0
        while any(p.any_active for p in self._pools.values()):
            if ticks >= max_ticks:
                live = [rid for p in self._pools.values()
                        for s, rid in enumerate(p.req_of_slot)
                        if rid is not None and bool(p.state.active[s])]
                raise RuntimeError(
                    f"run_to_completion hit max_ticks={max_ticks} with "
                    f"requests {live} still active (chunk_iters="
                    f"{self.cfg.chunk_iters}); raise max_ticks or maxiter")
            out.update(self.step())
            ticks += 1
        return out
