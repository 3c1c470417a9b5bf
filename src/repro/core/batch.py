"""Batched multi-system JPCG — B independent solves in ONE compiled loop.

The paper's Challenge 1 is "support an arbitrary problem and terminate
acceleration processing on the fly"; the serving-scale version of that
challenge is *many* arbitrary problems at once.  This module stacks B
independent SPD systems along a leading batch axis and solves them inside
one ``lax.while_loop`` through one of two engines:

* ``engine="vm"`` (default) — the batched stream VM
  (:mod:`repro.core.vm`) executing a compiled stream-ISA program
  (:func:`repro.core.compile.compile_policy`); ``policy=`` picks the VSR
  schedule ("paper" | "min_traffic") and ``program=`` injects any custom
  program.  By default the program is *specialized* into the executable
  at trace time (straight-line ops, cached per (bucket, backend, scheme,
  program bytes) — the fast path); ``specialize=False`` keeps the
  program a traced operand so the executable is cached per (bucket
  shape, backend, scheme) — **never** per program/policy — and swapping
  schedules never recompiles (the paper's
  one-bitstream-serves-any-schedule goal, kept where it matters).
* ``engine="phases"`` — the phase-fused loop
  (:func:`repro.core.phases.vsr_iteration`, literally the single-system
  iteration code), kept as the bit-exact oracle the VM is tested against.

Either engine runs the same masked per-lane loop:

* every lane carries its own ``active`` flag; a lane terminates on the
  fly at its own ``‖r‖² ≤ τ_g`` while the batch keeps iterating — its
  ``x/r/p`` freeze (masked update) and only the live lanes pay for new
  iterations being *observed* (the frozen lanes' arithmetic is dead
  compute on a SIMD machine either way, exactly like frozen decode slots
  in :class:`repro.serve.engine.DecodeEngine`);
* the loop exits when every lane is done or ``maxiter`` is reached.

Batch API
---------
>>> from repro.core.batch import jpcg_solve_batched
>>> results = jpcg_solve_batched([a1, a2, ...], tol=1e-12)
>>> results[0].x, results[0].iterations, results[0].converged

``problems`` is a sequence of :class:`~repro.sparse.csr.CSRMatrix` (or
square dense arrays); ``bs``/``x0s`` optionally give per-problem right-
hand sides / starts (defaults: all-ones / all-zeros, the paper's §7.1
protocol).  ``tol`` may be a scalar or a per-problem sequence.  Each
returned :class:`~repro.core.cg.CGResult` matches what the single-system
:func:`~repro.core.cg.jpcg_solve` would have produced for that lane (to
scheme tolerance; iteration counts agree within ±1).

Bucket policy / compile cache
-----------------------------
Heterogeneous problems are padded to a shared shape before stacking:
every structural dimension (rows, row-ELL / SELL widths, ELL slots) is
rounded UP to a power-of-two bucket edge
(:func:`repro.sparse.stacking.bucket_up`), and the Pallas ELLPACK
operand's row blocks, slabs and col tiles to an eighth-octave edge
(:func:`repro.sparse.stacking.fine_bucket_up`, < 12.5% padding), so
traffic whose sizes vary continuously collapses onto ``O(log n)``
distinct compiled shapes — the batched restatement of ``cg.py``'s "one
compiled program per padded bucket".  Executables are held in an
explicit cache keyed by ``(backend, batch, bucket dims, scheme, maxiter,
trace)``;
:func:`batch_cache_info` exposes hit/miss counts so tests (and the
serving engine) can assert reuse.

Running the tests without ``hypothesis``
----------------------------------------
The tier-1 suite imports ``given/settings/strategies`` from
``tests/_hyp.py``, which falls back to deterministic fixed-example
sampling when the real ``hypothesis`` package is absent — so
``PYTHONPATH=src python -m pytest -x -q`` runs green on a bare image;
see ``tests/README.md``.
"""
from __future__ import annotations

import itertools
from functools import partial
from typing import Any, List, NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import dfloat
from repro.core.cg import CGResult
from repro.core.dfloat import hi, select
from repro.core.metrics import (advance_status, finalize_status,
                                initial_status, is_breakdown,
                                solver_metrics, span, status_name,
                                tick_health)
from repro.core.phases import vsr_iteration
from repro.core.precision import PrecisionScheme, get_scheme
from repro.sparse.csr import CSRMatrix, csr_from_coo
from repro.sparse.ellpack import csr_to_ellpack
from repro.sparse.stacking import (choose_layout, stack_ellpack,
                                   stack_rowell, stack_sell)

__all__ = ["BatchedCGState", "jpcg_solve_batched", "batched_matvec_flat",
           "batched_matvec_rowell", "batched_matvec_sell",
           "batched_matvec_ellpack", "tree_sum", "rounded_products",
           "batch_cache_info", "batch_cache_clear"]


class BatchedCGState(NamedTuple):
    """Per-lane CG state, leading axis = batch."""

    k: jax.Array        # global loop counter (int32 scalar)
    it: jax.Array       # int32[G] per-lane iteration counts
    status: jax.Array   # int32[G] exit codes (repro.core.metrics.STATUS_*)
    x: jax.Array        # [G, n] solutions (frozen once a lane converges)
    r: jax.Array        # [G, n] residuals
    p: jax.Array        # [G, n] search directions
    rz: jax.Array       # [G]
    rr: jax.Array       # [G] per-lane ‖r‖² — the termination scalars
    active: jax.Array   # bool[G] live-lane mask
    trace: jax.Array    # [G, maxiter] rr per iteration, or [G, 0]
    rep: Any = None     # Replacement under a wide scheme, else None


class Replacement(NamedTuple):
    """Per-lane residual-replacement state of a wide scheme."""

    rr_ref: jax.Array   # float32[G]: ‖r‖² at the lane's last replacement
    count: jax.Array    # int32[G]: replacements so far


#: a lane replaces its residual once ‖r‖ fell below DELTA of its value at
#: the last replacement (squared: ‖r‖² below DELTA² of it)
DELTA = 0.1

#: a wide lane stops on the device at ‖r‖ ≤ MARGIN·√tol (``rr ≤ MARGIN²·tol``):
#: the caller gets the device's x rounded to float64, and checks it with a
#: float64 ``b − A·x`` that rounds again.  On ecology2's 1000 × 1000 grid
#: (‖x‖∞ = 1.2e6) the two add 2.8e-10 and 1.5e-10 of ‖b‖, so a lane stopped
#: at 1e-9 read 1.05e-9; at half of it the float64 x meets the bound
MARGIN = 0.5


def _row_dot(a: jax.Array, b: jax.Array) -> jax.Array:
    if isinstance(a, dfloat.DF):
        return a.dot(b)
    return jnp.sum(a * b, axis=-1)


def replace_residual(matvec, b, diag, x, r, rz, rr, rep: Replacement, upd,
                     tol):
    """Residual replacement for a wide scheme, after a tick's updates.

    A committed lane (``upd``) whose recurrence ``‖r‖²`` fell below
    ``DELTA²`` of its value at the last replacement, or met ``tol`` (the
    device's, already scaled by ``MARGIN²``), takes ``r = b − A·x`` from
    the same SpMV, and ``rz``/``rr`` from that ``r``; ``p`` is kept.  So
    a lane's ``rr`` meets ``tol`` at the end of a tick only on a
    replaced, true residual: the callers' ``rr ≤ tol`` tests then latch
    ``CONVERGED`` on it and on nothing else.  One conditional SpMV of
    every lane when any lane needs one; its other device work carries
    the scope ``vm_replace``.
    """
    with jax.named_scope("vm_replace"):
        rrh = hi(rr)
        need = upd & ((rrh <= DELTA * DELTA * rep.rr_ref) | (rrh <= tol))

        def true_residual(_):
            rt = b - matvec(x)
            return rt, _row_dot(rt, rt / diag), _row_dot(rt, rt)

        rt, rzt, rrt = jax.lax.cond(jnp.any(need), true_residual,
                                    lambda _: (r, rz, rr), None)
        r = select(need[:, None], rt, r)
        rz = select(need, rzt, rz)
        rr = select(need, rrt, rr)
        rep = Replacement(jnp.where(need, hi(rr), rep.rr_ref),
                          rep.count + need.astype(jnp.int32))
    return r, rz, rr, rep


# --------------------------------------------------------------- matvecs
def batched_matvec_flat(gcols, vals, rows, x, *, n_rows: int,
                        padded_cols: int,
                        scheme: PrecisionScheme) -> jax.Array:
    """Batched SpMV over packed nonzero streams (the XLA backend's M1).

    ``gcols/vals/rows`` are the [G, N] stacked streams of
    :func:`repro.sparse.stacking.stack_flat`; ``x`` is [G, n_rows].
    Gathers x per nonzero, multiplies at the scheme's accumulate dtype,
    and segment-sums into rows — value-identical to
    :func:`repro.core.operators.bell_spmv_jnp` lane by lane (same
    products in the same flattened (block, slab, slot) order), but with
    no [B, T, col_tile] x-tile intermediate.

    **Superseded in the hot path** by :func:`batched_matvec_rowell`: the
    per-nonzero ``segment_sum`` scatter costs ~100 ns/element on XLA
    CPU and dominated the whole iteration (the PR-7 "batched loop loses
    to the python loop by 30×" regression was entirely this op).  Kept
    as the stream-layout reference implementation.
    """
    acc = scheme.spmv_acc_dtype
    G = x.shape[0]
    k = min(x.shape[-1], padded_cols)
    x_in = x.astype(scheme.spmv_in_dtype)
    x_pad = jnp.zeros((G, padded_cols), x_in.dtype).at[:, :k].set(x_in[:, :k])
    xg = jnp.take_along_axis(x_pad, gcols, axis=1)
    prod = vals.astype(acc) * xg.astype(acc)
    seg = partial(jax.ops.segment_sum, num_segments=n_rows)
    y = jax.vmap(seg)(prod, rows)
    return y.astype(scheme.vector_dtype)


def tree_sum(p, axis: int):
    """Deterministic halving-tree reduction over ``axis``.

    ``jnp.sum``'s reduce tree depends on the axis *length* on XLA CPU,
    so trimming trailing zero slots changes result bits — exactly what
    sliced-ELL does to row-ELL's width.  This fold fixes the bracketing:
    pad to a power of two with exact zeros, then repeatedly add the top
    half onto the bottom half.  The bracketing is *suffix-stable* —
    ``T(2w) = T(w)(lo) + T(w)(hi)`` and an all-zero hi folds away
    exactly — so a row reduced at any padded width ≥ its nonzero count
    yields identical bits.  Row-ELL (global W), sliced-ELL (per-slice
    w ≤ W) and the numpy reference all reduce through this one function,
    which is what makes the layouts bit-interchangeable.  Works on
    numpy and jax arrays alike (slicing + ``+`` only).

    Callers that feed *products* into this tree must route them through
    :func:`rounded_products` — XLA:CPU otherwise contracts a bare
    multiply feeding the first fold into an FMA, and *which* shapes get
    contracted is a codegen detail (1-ulp layout-dependent drift,
    exactly what this function exists to prevent).
    """
    ndim = p.ndim
    axis = axis % ndim
    w = p.shape[axis]
    wp = 1 << max(w - 1, 0).bit_length()   # next pow2 (wp >= max(w, 1))
    if wp != w:
        xp = np if isinstance(p, np.ndarray) else jnp
        pad = [(0, 0)] * ndim
        pad[axis] = (0, wp - w)
        p = xp.pad(p, pad)
    w = wp
    ix = [slice(None)] * ndim
    while w > 1:
        h = w // 2
        lo, hi = list(ix), list(ix)
        lo[axis] = slice(0, h)
        hi[axis] = slice(h, w)
        p = p[tuple(lo)] + p[tuple(hi)]
        w = h
    ix[axis] = 0
    return p[tuple(ix)]


def rounded_products(vals, xg, acc):
    """``vals ⊙ xg`` at ``acc`` dtype, pinned to correctly-rounded bits.

    A bare ``v * x`` feeding an add is fair game for LLVM FMA
    contraction on XLA:CPU — the add absorbs the *infinitely precise*
    product, and whether that happens depends on the fused kernel's
    shape.  Row-ELL (width W) and sliced-ELL (width w ≤ W) compile to
    different shapes, so contraction showed up as a 1-ulp cross-layout
    drift (``lax.optimization_barrier`` and XLA fast-math flags do not
    stop it — it happens at LLVM codegen).  Adding a runtime ±0
    (``xg * 0``; opaque to the simplifier since x is a traced value)
    fixes it structurally: the only contractible multiply is consumed
    *here*, into an add whose other operand is zero — and
    ``fma(v, x, ±0) ≡ round(v·x)`` — so what reaches the
    :func:`tree_sum` folds is an add/fma result, never a bare multiply.
    Bit-exact whether or not the compiler contracts.
    """
    v = vals.astype(acc)
    g = xg.astype(acc)
    return v * g + g * jnp.zeros((), acc)


def batched_matvec_rowell(cols, vals, x, *,
                          scheme: PrecisionScheme) -> jax.Array:
    """Batched SpMV over row-major ELL lanes (the XLA backend's M1).

    ``cols/vals`` are the slot-major ``[G, W, n_pad]`` stacked arrays of
    :func:`repro.sparse.stacking.stack_rowell`; ``x`` is ``[G, n_pad]``.
    ``y[g, i] = Σ_w vals[g, w, i] · x[g, cols[g, w, i]]`` — a gather
    plus a :func:`tree_sum` over the width axis (each tree add is
    contiguous over the row lanes; the deterministic bracketing is what
    keeps row-ELL and sliced-ELL bit-identical).  No scatter anywhere:
    this is why one batched iteration costs arithmetic instead of
    ~100 ns/nonzero of XLA-CPU ``segment_sum`` (see
    :func:`batched_matvec_flat`).  Casts follow the scheme contract
    (matrix dtype on ``vals`` packed at rest by the stacker, ``spmv_in``
    on the gathered x, accumulate at ``spmv_acc``, result at
    ``vector``).  Its device operations carry the scope
    ``m1_xla_rowell``.
    """
    with jax.named_scope("m1_xla_rowell"):
        acc = scheme.spmv_acc_dtype
        x_in = x.astype(scheme.spmv_in_dtype)
        xg = jax.vmap(lambda xv, c: xv[c])(x_in, cols)    # [G, W, n_pad]
        y = tree_sum(rounded_products(vals, xg, acc), axis=1)
        return y.astype(scheme.vector_dtype)


def batched_matvec_sell(cols, vals, iperm, x, *, groups,
                        scheme: PrecisionScheme) -> jax.Array:
    """Batched SpMV over stacked SELL-C-σ lanes (the skewed-matrix M1).

    ``cols/vals`` are the flat slot-major ``[G, L]`` arrays of
    :func:`repro.sparse.stacking.stack_sell`, ``iperm`` the ``[G,
    n_pad]`` un-permutation, ``groups`` the static ``(rows, width)``
    runs.  Each width group is a small row-ELL rectangle: gather +
    :func:`tree_sum` over its own width.  Because the per-row slot order
    matches row-ELL and the tree bracketing is suffix-stable, the result
    is bit-identical to :func:`batched_matvec_rowell` on the same
    matrix — the layout choice is invisible to the solver trajectory.
    Its device operations carry the scope ``m1_xla_sell``.
    """
    with jax.named_scope("m1_xla_sell"):
        acc = scheme.spmv_acc_dtype
        x_in = x.astype(scheme.spmv_in_dtype)
        G = x.shape[0]
        parts, off = [], 0
        for rows, w in groups:
            if w == 0:
                parts.append(jnp.zeros((G, rows), acc))
                continue
            c = cols[:, off:off + rows * w].reshape(G, w, rows)
            v = vals[:, off:off + rows * w].reshape(G, w, rows)
            xg = jax.vmap(lambda xv, cc: xv[cc])(x_in, c)  # [G, w, rows]
            parts.append(tree_sum(rounded_products(v, xg, acc), axis=1))
            off += rows * w
        y_sorted = jnp.concatenate(parts, axis=1)          # [G, n_pad]
        y = jnp.take_along_axis(y_sorted, iperm, axis=1)
        return y.astype(scheme.vector_dtype)


def batched_matvec_ellpack_df(tile_cols, vals, local_cols, x, *,
                              col_tile: int, n_col_tiles: int,
                              interpret: bool) -> dfloat.DF:
    """Batched double-fp32 Pallas SpMV of a wide ``x`` (:class:`DF`, or
    :class:`TF` for the solution) -> :class:`DF`."""
    from repro.kernels.spmv_df import spmv_pallas_df_batched, x_words_tiles
    n = x.shape[-1]
    xt = x_words_tiles(x.planes, n_col_tiles, col_tile)
    yh, yl = spmv_pallas_df_batched(tile_cols, vals, local_cols, xt,
                                    words=len(x.planes), interpret=interpret)
    G = yh.shape[0]
    return dfloat.DF(yh.reshape(G, -1)[:, :n], yl.reshape(G, -1)[:, :n])


def batched_matvec_ellpack(tile_cols, vals, local_cols, x, *,
                           col_tile: int, n_col_tiles: int,
                           scheme: PrecisionScheme,
                           interpret: bool) -> jax.Array:
    """Batched Pallas SpMV (one kernel launch for all G systems)."""
    from repro.kernels.spmv import spmv_pallas_batched
    G = x.shape[0]
    padded_cols = n_col_tiles * col_tile
    k = min(x.shape[-1], padded_cols)
    x_pad = jnp.zeros((G, padded_cols), x.dtype).at[:, :k].set(x[:, :k])
    x_tiles = x_pad.reshape(G, n_col_tiles, col_tile)
    y = spmv_pallas_batched(tile_cols, vals, local_cols, x_tiles,
                            scheme=scheme, interpret=interpret)
    return y.reshape(G, -1)[:, : x.shape[-1]].astype(scheme.vector_dtype)


# ------------------------------------------------------- loop construction
def _batched_init(matvec, diag, b, x0, *, maxiter, scheme, with_trace,
                  tol, detect=True):
    vd = scheme.vector_dtype
    G = b.shape[0]
    r = b - matvec(x0)
    z = r / diag
    p = z
    rz = _row_dot(r, z)
    rr = _row_dot(r, r)
    trace = jnp.zeros((G, maxiter if with_trace else 0), dtype=vd)
    rep = None
    if scheme.wide:
        rep = Replacement(hi(rr), jnp.zeros(G, jnp.int32))
    return BatchedCGState(
        k=jnp.zeros((), jnp.int32), it=jnp.zeros(G, jnp.int32),
        status=initial_status(hi(rr), tol, detect=detect),
        x=x0, r=r, p=p, rz=rz, rr=rr, active=hi(rr) > tol, trace=trace,
        rep=rep)


def _batched_body(matvec, diag, tol, maxiter_vec=None, *, bound=None,
                  write_trace=True, detect=True, b=None):
    """Masked VSR iteration over all lanes.

    Frozen (converged) lanes still flow through the arithmetic — that is
    free on a SIMD device — but every state write is gated on ``active``,
    so their ``x`` stops updating the iteration they converge.  Division
    garbage a frozen lane may produce (0/0 in alpha/beta) is discarded by
    the same gates: ``where`` selects, it never blends.

    ``bound`` makes the tick *self-gating* so it can run inside an
    iteration chunk (:func:`_run_chunked`): the tick is a no-op — no
    state write, no ``k``/``it`` advance — once every lane converged or
    ``k`` reached ``bound``, which is exactly the predicate the
    ``while_loop`` ``cond`` checks.  Evaluating it per tick instead of
    per chunk is what keeps chunked execution bit-identical to k=1 in
    *every* observable, including iteration counts.  ``write_trace=False``
    suppresses the per-tick trace scatter (the chunked runner hoists it
    to one blend per chunk).

    ``detect`` arms in-loop breakdown detection
    (:func:`repro.core.metrics.tick_health` on the tick's own
    ``pAp``/``α``/``β``/``rr`` — no extra arithmetic): a lane that trips
    it freezes *this* tick — writes discarded, ``it`` not advanced,
    ``status`` latched to the breakdown code, lane deactivated.  Healthy
    lanes see the identical dataflow with or without detection (the
    commit mask degenerates to ``keep``), which ``tests/test_health.py``
    locks bit-for-bit.

    ``b`` (a wide scheme's right-hand side) arms
    :func:`replace_residual` after the tick's updates.
    """

    def body(s: BatchedCGState) -> BatchedCGState:
        x_new, r_new, p_new, rz_new, rr_new, (pap, alpha, beta) = \
            vsr_iteration(matvec, diag, s.x, s.r, s.p, s.rz, dot=_row_dot,
                          with_aux=True)
        go = jnp.any(s.active)
        if bound is not None:
            go = go & (s.k < bound)
        keep = s.active & go
        upd, bd_i, bd_n = tick_health(keep, hi(pap), hi(alpha), hi(beta),
                                      hi(rr_new), detect=detect)
        rep = s.rep
        if b is not None:
            r_new, rz_new, rr_new, rep = replace_residual(
                matvec, b, diag, x_new, r_new, rz_new, rr_new, rep, upd, tol)
        kv = upd[:, None]
        x = select(kv, x_new, s.x)
        r = select(kv, r_new, s.r)
        p = select(kv, p_new, s.p)
        rz = select(upd, rz_new, s.rz)
        rr = select(upd, rr_new, s.rr)
        rr_new = hi(rr_new)
        it = s.it + upd.astype(jnp.int32)
        if write_trace and s.trace.shape[1]:
            safe_k = jnp.minimum(s.k, s.trace.shape[1] - 1)
            trace = s.trace.at[:, safe_k].set(
                jnp.where(upd & (s.k < s.trace.shape[1]), rr_new,
                          s.trace[:, safe_k]))
        else:
            trace = s.trace
        live = hi(rr) > tol
        if maxiter_vec is not None:
            live = live & (it < maxiter_vec)
        if detect:
            live = live & ~(bd_i | bd_n)
        status = advance_status(s.status, upd=upd, bd_indef=bd_i,
                                bd_nonf=bd_n, rr_new=rr_new, tol=tol,
                                it=it, maxiter_vec=maxiter_vec)
        # a no-op tick (go=False) must not re-evaluate liveness
        active = jnp.where(keep, live, s.active)
        return BatchedCGState(k=s.k + go.astype(jnp.int32), it=it,
                              status=status, x=x, r=r, p=p, rz=rz, rr=rr,
                              active=active, trace=trace, rep=rep)

    return body


# -------------------------------------------------------- chunked execution
def _run_chunked(cond, tick, st, *, steps: int, with_trace: bool,
                 maxiter: int, rr_of):
    """Drive ``tick`` to completion, ``steps`` ticks per ``while_loop``
    body (the iteration-chunking knob, ISSUE 7).

    The termination predicate — a host-visible sync on XLA CPU — is
    evaluated once per *chunk*; each tick inside the chunk self-gates
    (see ``bound=`` on the tick builders), so results stay bit-identical
    to ``steps=1`` in every observable: a lane freezes the tick it
    converges, ``k``/``it`` never overshoot, and trailing in-chunk ticks
    after global convergence are discarded no-ops.

    With ``with_trace`` the per-tick trace scatter is *hoisted*: ticks
    run with ``write_trace=False`` while the chunk accumulates the
    ``steps × G`` post-tick ``rr`` values (via ``rr_of``) and advance
    flags, then blends them into the trace with one dynamic slice per
    chunk.  Because every non-final chunk advances ``k`` by exactly
    ``steps``, each chunk starts at a multiple of ``steps`` — the trace
    is padded up to a whole number of chunks and cropped on exit.
    """
    if steps <= 1:
        return jax.lax.while_loop(cond, tick, st)
    if not with_trace:
        def body(s):
            return jax.lax.fori_loop(0, steps, lambda _, ss: tick(ss), s)
        return jax.lax.while_loop(cond, body, st)

    G, width = st.trace.shape
    n_chunks = -(-maxiter // steps)
    padded = n_chunks * steps
    st = st._replace(trace=jnp.pad(st.trace, ((0, 0), (0, padded - width))))

    def body(s):
        zero = jnp.zeros((), s.k.dtype)
        k0 = s.k

        def inner(i, carry):
            ss, rrb, adv = carry
            s2 = tick(ss)
            rrb = rrb.at[i].set(rr_of(s2))
            adv = adv.at[i].set(s2.it != ss.it)   # == this tick's keep mask
            return s2, rrb, adv

        rrb0 = jnp.zeros((steps, G), s.trace.dtype)
        adv0 = jnp.zeros((steps, G), bool)
        s, rrb, adv = jax.lax.fori_loop(0, steps, inner, (s, rrb0, adv0))
        old = jax.lax.dynamic_slice(s.trace, (zero, k0), (G, steps))
        blk = jnp.where(adv.T, rrb.T, old)
        return s._replace(
            trace=jax.lax.dynamic_update_slice(s.trace, blk, (zero, k0)))

    out = jax.lax.while_loop(cond, body, st)
    return out._replace(trace=out.trace[:, :width])


# ------------------------------------------------------------ compile cache
_CACHE: dict = {}
_CACHE_STATS = {"hits": 0, "misses": 0}
#: ids of batch calls: the ``rid`` of each call's spans
_CALL_IDS = itertools.count()


def batch_cache_info() -> dict:
    """Executable-cache statistics: {entries, hits, misses}."""
    return {"entries": len(_CACHE), **_CACHE_STATS}


def batch_cache_clear() -> None:
    _CACHE.clear()
    _CACHE_STATS.update(hits=0, misses=0)


def _cached(key, make):
    fn = _CACHE.get(key)
    if fn is None:
        _CACHE_STATS["misses"] += 1
        fn = _CACHE[key] = make()
    else:
        _CACHE_STATS["hits"] += 1
    return fn


def _matvec_factory(*, backend, scheme, layout=None, groups=None,
                    block_rows=None, col_tile=None, n_col_tiles=None,
                    interpret=False):
    """``matvec_of(mat) -> matvec`` closure for one backend + bucket shape.

    Shared by the solve-to-completion runner and the serving stepper so
    both paths are guaranteed to compute the same M1.  ``layout`` picks
    the matrix operand format: ``"rowell"`` (XLA default, ``mat = (cols,
    vals)`` slot-major ``[G, W, n_pad]``), ``"sell"`` (either backend,
    ``mat = (cols, vals, iperm)`` with static ``groups``), or
    ``"ellpack"`` (Pallas default, the tiled 3-tuple).  The operands
    carry their own shapes — the kernel-tiling parameters only matter
    for the Pallas ellpack path.
    """
    layout = layout or ("rowell" if backend == "xla" else "ellpack")
    if scheme.wide:
        if (backend, layout) != ("pallas", "ellpack"):
            raise ValueError(f"scheme {scheme.name!r} runs on the Pallas "
                             "ELLPACK path only")

        def matvec_of(mat):
            tc, v, lc = mat
            return lambda x: batched_matvec_ellpack_df(
                tc, v, lc, x, col_tile=col_tile, n_col_tiles=n_col_tiles,
                interpret=interpret)
        return matvec_of
    if layout == "sell":
        if groups is None:
            raise ValueError("layout='sell' needs the static groups= "
                             "signature of the stacked operand")
        if backend == "xla":
            def matvec_of(mat):
                cols, vals, iperm = mat
                return lambda x: batched_matvec_sell(
                    cols, vals, iperm, x, groups=groups, scheme=scheme)
        elif backend == "pallas":
            def matvec_of(mat):
                from repro.kernels.spmv import spmv_pallas_sell
                cols, vals, iperm = mat
                return lambda x: jnp.take_along_axis(
                    spmv_pallas_sell(cols, vals, x, groups=groups,
                                     scheme=scheme, interpret=interpret),
                    iperm, axis=1).astype(scheme.vector_dtype)
        else:
            raise ValueError(f"unknown backend {backend!r}")
    elif backend == "xla" and layout == "rowell":
        def matvec_of(mat):
            cols, vals = mat
            return lambda x: batched_matvec_rowell(cols, vals, x,
                                                   scheme=scheme)
    elif backend == "pallas" and layout == "ellpack":
        def matvec_of(mat):
            tc, v, lc = mat
            return lambda x: batched_matvec_ellpack(
                tc, v, lc, x, col_tile=col_tile, n_col_tiles=n_col_tiles,
                scheme=scheme, interpret=interpret)
    else:
        raise ValueError(f"unsupported backend/layout combination "
                         f"{backend!r}/{layout!r}")
    return matvec_of


def _make_runner(*, backend, scheme, maxiter, with_trace, layout=None,
                 groups=None, block_rows=None, col_tile=None,
                 n_col_tiles=None, steps_per_sync=8, donate=False,
                 detect=True, interpret=False, mesh=None):
    """Build the jitted solve-to-completion runner for one bucket shape.

    ``steps_per_sync`` = iterations per termination-predicate sync (the
    chunking knob; bit-identical for any value).  ``donate`` marks the
    ``b``/``x0`` operands donated (off by default — see
    :func:`jpcg_solve_batched`).  ``detect`` arms breakdown detection
    (see :func:`_batched_body`); either way leftover ``RUNNING`` statuses
    are finalized to ``MAXITER`` before the state is returned — a solve
    runner's loop only exits with everything terminal or the budget
    spent.  ``mesh`` shards the operands' lane axis over a device mesh
    before the jitted call (:mod:`repro.core.shard`); lanes are
    independent, so the sharded runner is bit-identical to the
    single-device one.
    """
    matvec_of = _matvec_factory(
        backend=backend, scheme=scheme, layout=layout, groups=groups,
        block_rows=block_rows, col_tile=col_tile,
        n_col_tiles=n_col_tiles, interpret=interpret)
    hoist_trace = with_trace and steps_per_sync > 1

    def run(mat, diag, b, x0, tol):
        matvec = matvec_of(mat)
        st = _batched_init(matvec, diag, b, x0, maxiter=maxiter,
                           scheme=scheme, with_trace=with_trace, tol=tol,
                           detect=detect)
        tick = _batched_body(matvec, diag, tol, bound=maxiter,
                             write_trace=not hoist_trace, detect=detect,
                             b=b if scheme.wide else None)

        def cond(s):
            return (s.k < maxiter) & jnp.any(s.active)

        out = _run_chunked(cond, tick, st, steps=steps_per_sync,
                           with_trace=with_trace, maxiter=maxiter,
                           rr_of=lambda s: hi(s.rr))
        return out._replace(status=finalize_status(out.status))

    fn = jax.jit(run, donate_argnums=(2, 3) if donate else ())
    if mesh is None:
        return fn
    from repro.core.shard import place_lanes

    def run_sharded(mat, diag, b, x0, tol):
        return fn(place_lanes(mesh, mat), place_lanes(mesh, diag),
                  place_lanes(mesh, b), place_lanes(mesh, x0),
                  place_lanes(mesh, tol))

    return run_sharded


# ---------------------------------------------------------------- public
def _as_csr(a) -> CSRMatrix:
    if isinstance(a, CSRMatrix):
        return a
    arr = np.asarray(a)
    if arr.ndim == 2 and arr.shape[0] == arr.shape[1]:
        rows, cols = np.nonzero(arr)
        return csr_from_coo(rows, cols, arr[rows, cols], arr.shape)
    raise TypeError(f"cannot batch-solve a {type(a)}")


def _pad_stack(vecs: Sequence[np.ndarray], n_pad: int, fill: float,
               dtype) -> jnp.ndarray:
    """The lanes' vectors padded with ``fill`` to ``[G, n_pad]`` on the
    device at ``dtype``; ``dtype`` :class:`~repro.core.dfloat.DF` /
    :class:`~repro.core.dfloat.TF` gives their fp32 words."""
    out = np.full((len(vecs), n_pad), fill, dtype=np.float64)
    for g, v in enumerate(vecs):
        out[g, : v.shape[0]] = np.asarray(v, dtype=np.float64)
    if dtype is dfloat.DF:
        return dfloat.df_from_f64(out)
    if dtype is dfloat.TF:
        return dfloat.tf_from_f64(out)
    return jnp.asarray(out, dtype=dtype)


def jpcg_solve_batched(problems: Sequence, bs: Optional[Sequence] = None,
                       x0s: Optional[Sequence] = None, *,
                       tol=1e-12, maxiter: int = 20_000,
                       scheme="mixed_v3", backend: str = "xla",
                       engine: str = "vm", policy: Optional[str] = None,
                       program: Optional[np.ndarray] = None,
                       specialize: bool = True,
                       block_rows: int = 256, col_tile: int = 512,
                       bucket: bool = True, layout: str = "auto",
                       with_trace: bool = False,
                       steps_per_sync: int = 8, donate: bool = False,
                       detect: bool = True, with_status: bool = True,
                       interpret: Optional[bool] = None,
                       mesh=None) -> List[CGResult]:
    """Solve B independent SPD systems in one compiled ``lax.while_loop``.

    See the module docstring for the batch API, bucket policy, and the
    ``engine``/``policy``/``program`` knobs (default: the batched stream
    VM running the compiled paper-policy program; ``policy``/``program``
    only make sense with ``engine="vm"`` and are rejected otherwise —
    the phases engine hard-codes its schedule).  ``specialize`` (default
    True) unrolls the program into the executable at trace time — the
    fast straight-line path, cached per program bytes;
    ``specialize=False`` keeps the program a traced operand so one
    executable serves every program of the same padded length.  Lanes
    terminate on the fly at their own ``‖r‖² ≤ tol_g``; the compiled
    loop runs until every lane converged or ``maxiter``.

    ``steps_per_sync`` (static, joins the executable cache key) is the
    iteration-chunking knob: the loop syncs its termination predicate
    with the host once per that many iterations.  Any value produces
    bit-identical results — each in-chunk tick self-gates (see
    :func:`_batched_body`) — so the default 8 trades nothing but
    predicate-sync latency.  ``donate`` marks the fresh ``b``/``x0``
    operands donated; it's off by default because a solve-to-completion
    call consumes them *inside* the computation (XLA's own liveness
    already reuses the buffers) and would only warn that no output can
    alias them — donation earns its keep on the serving steppers, whose
    state argument round-trips through the jit boundary every tick.

    ``layout`` picks the stacked matrix format: ``"auto"`` (default)
    applies the padding-ratio heuristic
    (:func:`repro.sparse.stacking.choose_layout` — sliced-ELL when
    ``Σ n·W / Σ nnz`` exceeds
    :data:`~repro.sparse.stacking.SELL_PADDING_THRESHOLD`, else the
    backend default), ``"rowell"`` / ``"sell"`` force it on the XLA
    backend, ``"ellpack"`` / ``"sell"`` on Pallas.  Values are packed at
    ``scheme.matrix_dtype`` and indices at int16/int32 by ``n_pad`` at
    stacking time; the layout and index width join the executable cache
    key.  On the Pallas ELLPACK path lanes that hold one operator (the
    same ``CSRMatrix``, or bit-equal ones) share one pack, which the
    device copies to each of them.  Every layout is bit-identical to
    every other for the same scheme (shared :func:`tree_sum` reduction
    bracketing).

    ``detect`` (default True; static, joins the cache key) arms in-loop
    breakdown detection: a lane whose tick produces ``pAp ≤ 0`` or a
    non-finite ``rr``/``α``/``β`` freezes immediately with a breakdown
    status instead of spinning to ``maxiter`` — bit-invisible to healthy
    lanes (see :mod:`repro.core.metrics`).  ``with_status`` (default
    True) reports each lane's exit as ``CGResult.status``
    (``"CONVERGED"`` / ``"MAXITER"`` / ``"BREAKDOWN_INDEFINITE"`` /
    ``"BREAKDOWN_NONFINITE"``); ``with_status=False`` restores the
    legacy ``status=None`` result for callers that compare results
    structurally.  Each call also feeds the process-wide
    :func:`repro.core.metrics.solver_metrics` counters (iterations,
    SpMV-call and streamed-byte estimates, exit histogram).

    A wide scheme (``tpu_v3_df``: double-fp32 vectors, x in three fp32
    words; :mod:`repro.core.dfloat`) runs the Pallas ELLPACK path under
    the phases engine or the specialized VM, without a mesh.  Its lanes
    replace their residual on the device (:func:`replace_residual`),
    ``CONVERGED`` means a replaced residual met ``MARGIN²·tol`` (so the
    float64 ``x`` meets ``tol``), ``rr`` is that residual, each result's
    ``replacements`` counts them, and ``x`` comes back as float64.

    ``mesh`` (a 1-D :class:`jax.sharding.Mesh`, e.g.
    :func:`repro.core.shard.lane_mesh`) shards the *lane* axis over D
    devices: operands are laid out with ``NamedSharding`` over the
    ``lanes`` axis and the batch is padded up to a multiple of D with
    inert identity lanes (converged at admission, dropped from the
    results).  Lanes are independent, so the sharded solve is
    **bit-identical** to ``mesh=None`` for every scheme × layout ×
    engine (locked by ``tests/test_shard.py``); the mesh signature
    joins the executable cache key, so single-device and sharded
    executables never collide.
    """
    if engine != "vm" and (policy is not None or program is not None):
        raise ValueError(
            f"policy=/program= select the stream-VM's program; they have "
            f"no effect under engine={engine!r} — drop them or use "
            "engine='vm'")
    if policy is not None and program is not None:
        raise ValueError("pass either policy= (compiled for you) or "
                         "program= (pre-assembled), not both")
    scheme = get_scheme(scheme)
    if (scheme.vector_dtype == jnp.float64
            and not jax.config.read("jax_enable_x64")):
        raise RuntimeError(
            f"scheme {scheme.name!r} needs fp64 vectors: enable x64 first "
            "or use a TPU-tier scheme (tpu_v3, ...).")
    if scheme.wide:
        if (mesh is not None or backend != "pallas"
                or layout not in ("auto", "ellpack")
                or (engine == "vm" and not specialize)):
            raise ValueError(
                f"scheme {scheme.name!r} runs on backend='pallas', layout "
                "'ellpack', the phases engine or the specialized VM, no "
                "mesh")
        layout = "ellpack"
    if interpret is None:
        from repro.kernels.ops import default_interpret
        interpret = default_interpret()
    rid = next(_CALL_IDS)
    with span("batch.solve", rid=rid):
        with span("batch.prepare", rid=rid):
            bag = _prepare(problems, bs, x0s, tol, scheme=scheme,
                           backend=backend, layout=layout, bucket=bucket,
                           block_rows=block_rows, col_tile=col_tile,
                           mesh=mesh, rid=rid)
        if bag is None:
            return []
        key, make, args, method = _runner(
            bag, engine=engine, policy=policy, program=program,
            specialize=specialize, scheme=scheme, backend=backend,
            maxiter=maxiter, with_trace=with_trace, block_rows=block_rows,
            col_tile=col_tile, steps_per_sync=steps_per_sync,
            donate=donate, detect=detect, interpret=interpret, mesh=mesh)
        with span("batch.launch", rid=rid):
            st = _cached(key, make)(*args)
            if engine == "vm":
                from repro.core.isa import BUF, SREG
                xs = st.mem[BUF["x"]]
                rrs_dev, trace_dev = st.sregs[SREG["rr"]], st.trace
            else:
                xs, rrs_dev, trace_dev = st.x, st.rr, st.trace
        with span("batch.wait", rid=rid):
            its = np.asarray(st.it)
        with span("batch.results", rid=rid):
            if scheme.wide:
                xs, rrs = dfloat.to_f64(xs), dfloat.to_f64(rrs_dev)
                reps = np.asarray(st.rep.count)
            else:
                rrs, reps = np.asarray(rrs_dev), None
            return _results(bag, its, rrs, np.asarray(st.status), xs,
                            trace_dev, reps, scheme=scheme, method=method,
                            with_trace=with_trace, with_status=with_status)


class _Bag(NamedTuple):
    """A batch call's operands on the device, and the shapes its results
    and its executable's key need."""

    G: int                  # lanes, shard padding included
    G_real: int             # lanes of the caller's problems
    operands: int           # operands packed on the host for the G lanes
    ns: List[int]           # each lane's rows
    layout: str
    groups: Optional[tuple]
    n_col_tiles: Optional[int]
    bucket_dims: tuple
    index_bytes: int
    mat: tuple
    diag: jax.Array
    b: jax.Array
    x0: jax.Array
    tol: jax.Array


def _same_operator(a: CSRMatrix, b: CSRMatrix) -> bool:
    """Whether two lanes hold the same operator: the same object, or
    equal shape and nnz and bit-equal ``indptr``, ``indices`` and
    ``data``."""
    def bits(v):
        return np.ascontiguousarray(v).view(np.uint8)

    return a is b or (
        a.shape == b.shape and a.nnz == b.nnz
        and np.array_equal(a.indptr, b.indptr)
        and np.array_equal(a.indices, b.indices)
        and a.data.dtype == b.data.dtype
        and np.array_equal(bits(a.data), bits(b.data)))


def _distinct_operators(csrs):
    """``(ops, lane_to_op)``: the lanes' distinct operators in order of
    first appearance, and the index into ``ops`` of each lane's own."""
    ops, lane_to_op = [], []
    for a in csrs:
        k = next((k for k, o in enumerate(ops) if _same_operator(a, o)),
                 len(ops))
        if k == len(ops):
            ops.append(a)
        lane_to_op.append(k)
    return ops, tuple(lane_to_op)


def _at_matrix_dtype(a: CSRMatrix, scheme: PrecisionScheme) -> CSRMatrix:
    """``a`` with its values rounded on the host as a put and a device
    cast round them: to the dtype ``jnp.asarray`` gives them (float32
    unless x64 is on), then to the scheme's matrix dtype, each step to
    nearest even."""
    put = jax.dtypes.canonicalize_dtype(a.data.dtype)
    mat = jax.dtypes.canonicalize_dtype(scheme.matrix_dtype)
    return CSRMatrix(a.indptr, a.indices,
                     a.data.astype(put, copy=False).astype(mat, copy=False),
                     a.shape)


def _pack(csrs, ops, *, backend, layout, scheme, bucket, block_rows,
          col_tile):
    """Stack the matrices on the host in ``layout``: returns the stacked
    operand, its host arrays and its static shape signature.  SELL and
    row-ELL stack every lane of ``csrs``; ELLPACK stacks each of the
    lanes' distinct operators ``ops`` once, at the scheme's matrix
    dtype, and the put copies them to their lanes on the device."""
    groups = n_col_tiles = None
    if layout == "sell":
        stacked = stack_sell(csrs, bucket=bucket, scheme=scheme)
        host = (stacked.cols, stacked.vals, stacked.iperm)
        groups = stacked.groups
        # flat ints only: executable_key ravels the bucket dims
        bucket_dims = (stacked.padded_rows,
                       *(d for rw in groups for d in rw))
        index_bytes = stacked.index_bytes
    elif backend == "xla" and layout == "rowell":
        stacked = stack_rowell(csrs, bucket=bucket, scheme=scheme)
        host = (stacked.cols, stacked.vals)
        bucket_dims = (stacked.padded_rows, stacked.width)
        index_bytes = stacked.index_bytes
    elif backend == "pallas" and layout == "ellpack":
        stacked = stack_ellpack(
            [csr_to_ellpack(_at_matrix_dtype(a, scheme),
                            block_rows=block_rows, col_tile=col_tile)
             for a in ops], bucket=bucket)
        host = (stacked.tile_cols, stacked.vals, stacked.local_cols)
        n_col_tiles = stacked.n_col_tiles
        bucket_dims = stacked.vals.shape[1:]
        index_bytes = int(stacked.local_cols.dtype.itemsize)
    else:
        raise ValueError(f"unsupported backend/layout combination "
                         f"{backend!r}/{layout!r}")
    return stacked, host, groups, n_col_tiles, bucket_dims, index_bytes


def _prepare(problems, bs, x0s, tol, *, scheme, backend, layout, bucket,
             block_rows, col_tile, mesh, rid) -> Optional[_Bag]:
    """Choose the layout, pack the lanes (ELLPACK: each distinct
    operator once) and put every operand on the device (spans
    ``batch.layout``, ``batch.pack``, ``batch.put``); ``None`` for an
    empty bag."""
    csrs = [_as_csr(a) for a in problems]
    G = len(csrs)
    if G == 0:
        return None
    if backend not in ("xla", "pallas"):
        raise ValueError(f"unknown backend {backend!r}")
    if layout in (None, "auto"):
        with span("batch.layout", rid=rid):
            layout = choose_layout(
                csrs, default="rowell" if backend == "xla" else "ellpack")
    # Lane sharding: NamedSharding needs the lane axis divisible by the
    # shard count, so the bag is padded with inert identity lanes
    # (b = x0 = 0 -> rr = 0, converged at admission, dropped from the
    # results).  Padding happens after the layout heuristic so the
    # choice is driven by the real problems only.
    G_real = G
    if mesh is not None:
        from repro.core.shard import pad_lanes
        G = pad_lanes(G, mesh)
        if G != G_real:
            csrs = csrs + [_as_csr(np.eye(1))] * (G - G_real)
    with span("batch.pack", rid=rid):
        ops, lane_to_op = (_distinct_operators(csrs) if layout == "ellpack"
                           else (csrs, tuple(range(G))))
        stacked, host, groups, n_col_tiles, bucket_dims, index_bytes = \
            _pack(csrs, ops, backend=backend, layout=layout, scheme=scheme,
                  bucket=bucket, block_rows=block_rows, col_tile=col_tile)

    n_pad = stacked.padded_rows
    ns = [a.shape[0] for a in csrs]
    bs = list(bs) if bs is not None else [np.ones(n) for n in ns[:G_real]]
    x0s = (list(x0s) if x0s is not None
           else [np.zeros(n) for n in ns[:G_real]])
    for name, seq in (("bs", bs), ("x0s", x0s)):
        if len(seq) != G_real:
            raise ValueError(
                f"{name} has {len(seq)} entries for {G_real} problems")
        for g, v in enumerate(seq):
            if np.shape(v) != (ns[g],):
                raise ValueError(
                    f"{name}[{g}] has shape {np.shape(v)}, expected "
                    f"({ns[g]},) for problem {g}")
    if G != G_real:
        # Shard-padding lanes: zero rhs/start on the identity dummy.
        bs = bs + [np.zeros(1)] * (G - G_real)
        x0s = x0s + [np.zeros(1)] * (G - G_real)
    if np.ndim(tol) != 0 and len(tol) != G_real:
        raise ValueError(f"tol has {len(tol)} entries for {G_real} problems")

    vd = scheme.vector_dtype
    vec, sol = (dfloat.DF, dfloat.TF) if scheme.wide else (vd, vd)
    with span("batch.put", rid=rid):
        mat = tuple(jnp.asarray(h) for h in host)
        if len(ops) < G:
            # the lanes' operands, copied from the distinct ones on device
            idx = jnp.asarray(lane_to_op, jnp.int32)
            mat = tuple(jnp.take(m, idx, axis=0, mode="clip") for m in mat)
        # Padded rows get a unit diagonal and zero rhs: their residual is
        # identically zero, so they never influence rr or termination.
        diags = [a.diagonal() for a in ops]
        diag = _pad_stack([diags[k] for k in lane_to_op], n_pad, 1.0, vec)
        b = _pad_stack(bs, n_pad, 0.0, vec)
        x0 = _pad_stack(x0s, n_pad, 0.0, sol)
        if np.ndim(tol) == 0:
            tol_vec = jnp.full(G, float(tol), vd)
        else:
            tol_vec = jnp.asarray(
                np.concatenate([np.asarray(tol, np.float64),
                                np.ones(G - G_real)]), vd)
        if scheme.wide:
            tol_vec = tol_vec * (MARGIN * MARGIN)
    return _Bag(G=G, G_real=G_real, operands=len(ops), ns=ns,
                layout=layout, groups=groups,
                n_col_tiles=n_col_tiles, bucket_dims=bucket_dims,
                index_bytes=index_bytes, mat=mat, diag=diag, b=b, x0=x0,
                tol=tol_vec)


def _runner(bag: _Bag, *, engine, policy, program, specialize, scheme,
            backend, maxiter, with_trace, block_rows, col_tile,
            steps_per_sync, donate, detect, interpret, mesh):
    """``(cache key, make, args, method)``: the executable for the bag
    (``make`` builds it on a cache miss) and the operands it takes."""
    operands = (bag.mat, bag.diag, bag.b, bag.x0, bag.tol)
    key_kw = dict(
        backend=backend, scheme=scheme.name, batch=bag.G,
        bucket=bag.bucket_dims, layout=bag.layout,
        index_bytes=bag.index_bytes, maxiter=maxiter, with_trace=with_trace,
        steps_per_sync=steps_per_sync, donate=donate, detect=detect,
        interpret=interpret, mesh=mesh)
    runner_kw = dict(
        backend=backend, scheme=scheme, maxiter=maxiter,
        with_trace=with_trace, layout=bag.layout, groups=bag.groups,
        block_rows=block_rows, col_tile=col_tile,
        n_col_tiles=bag.n_col_tiles, steps_per_sync=steps_per_sync,
        donate=donate, detect=detect, interpret=interpret, mesh=mesh)
    from repro.core.compile import executable_key
    if engine == "phases":
        key = executable_key("solve", **key_kw)
        return (key, lambda: _make_runner(**runner_kw), operands,
                "vsr_batched")
    if engine != "vm":
        raise ValueError(f"unknown engine {engine!r}")
    # Specialized (default): the program is unrolled into the
    # executable, so its bytes join the cache key (via program_token)
    # — word-identical programs share one executable.  Generic
    # fallback: the executable is keyed on the bucket — NOT on the
    # program or policy; the program is a runtime operand (program
    # *length* participates only through the operand's shape).
    from repro.core.compile import canonical_program
    from repro.core.vm import make_vm_runner
    if program is None:
        policy = "paper" if policy is None else policy
        program = canonical_program(policy)
        method = f"vm_batched[{policy}]"
    else:
        method = "vm_batched[custom]"
    prog_np = np.asarray(program, np.int32)
    if specialize:
        key = executable_key("vm_solve_spec", program=prog_np, **key_kw)
        return (key, lambda: make_vm_runner(program=prog_np, **runner_kw),
                operands, method)
    key = executable_key("vm_solve", **key_kw)
    return (key, lambda: make_vm_runner(**runner_kw),
            (jnp.asarray(prog_np),) + operands, method + "|generic")


def _results(bag: _Bag, its, rrs, statuses, xs, trace_dev, reps, *, scheme,
             method, with_trace, with_status) -> List[CGResult]:
    """Feed the call's counters and build one ``CGResult`` per lane;
    ``reps`` are a wide scheme's replacements per lane, else None."""
    G, G_real, mat = bag.G, bag.G_real, bag.mat
    tols = np.asarray(bag.tol)

    # Observability (estimates, host-side): one SpMV per warm-up, per
    # committed iteration, and per discarded breakdown tick; streamed
    # bytes = events x the lane's at-rest nonzero stream (values +
    # indices as packed — padding already included, so this IS
    # nonzero_stream_bytes x padding_ratio x nnz).
    m = solver_metrics()
    if bag.layout == "ellpack":
        lane_stream_bytes = (mat[1].nbytes + mat[2].nbytes) // G
    else:
        lane_stream_bytes = (mat[0].nbytes + mat[1].nbytes) // G
    # A breakdown lane spent one discarded tick iff it actually entered
    # the loop: an in-loop breakdown freezes at its pre-tick rr (always
    # finite), while a lane latched non-finite at admission keeps its
    # non-finite warm-up rr and never ticked.  Shard-padding lanes
    # (g >= G_real) are inert and invisible to the accounting.
    n_bd = int(sum(is_breakdown(int(c)) and np.isfinite(rrs[g])
                   for g, c in enumerate(statuses[:G_real])))
    spmv_events = G_real + int(its[:G_real].sum()) + n_bd
    if reps is not None:
        m.bump("replacements", int(reps[:G_real].sum()))
        spmv_events += int(reps[:G_real].sum())
    m.bump("solves")
    m.bump("lanes", G_real)
    m.bump("operands_packed", bag.operands)
    m.bump("iterations", int(its[:G_real].sum()))
    m.bump("spmv_calls", spmv_events)
    m.bump("bytes_streamed_est", spmv_events * int(lane_stream_bytes))
    m.record_exits(statuses[:G_real])

    results = []
    for g in range(G_real):
        trace = (np.asarray(trace_dev[g])[: its[g]] if with_trace else None)
        results.append(CGResult(
            x=xs[g, : bag.ns[g]], iterations=int(its[g]), rr=float(rrs[g]),
            converged=bool(rrs[g] <= tols[g]), residual_trace=trace,
            scheme=scheme.name, method=method,
            status=status_name(int(statuses[g])) if with_status else None,
            replacements=0 if reps is None else int(reps[g])))
    return results
