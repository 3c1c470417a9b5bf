"""Fused VSR phase kernels — the paper's §5 streaming reuse, made explicit.

One kernel per JPCG phase: every vector the phase touches streams through
VMEM exactly once, all consumer "modules" of that phase read it from the
same resident tile, and intermediates that the schedule marks
``never_stored`` (``z``) exist only inside the kernel.  FIFO depth ≈ the
implicit double buffer Pallas allocates per BlockSpec operand.

* **phase2**: M4 (r' = r − α·ap), M8 (rr, hoisted for early termination),
  M5 (z = r'/M, never stored), M6 (rz) — reads r, ap, M once; writes r'
  once (min-traffic policy: the store the FPGA's FSM port wiring forbids,
  legal here); emits the two scalars in lane-parallel accumulators like
  :mod:`repro.kernels.dot`.
* **phase3**: M5-recompute (z = r'/M, §5.3), M7 (p' = z + β·p), M3
  (x' = x + α·p) — reads r', M, p, x once; writes p', x' once; the ``p``
  stream is shared by M7 and M3 (one read, two consumers — the VecCtrl-p
  duplication of paper Fig. 6).

HBM traffic for the fused loop body (per element, vector streams only):
phase1 SpMV reads + ap write, phase2 3R+1W, phase3 4R+2W — the 13-access
min-traffic schedule computed by :mod:`repro.core.vsr`.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.dot import DOT_BLOCK, _emit, _pad2d, _scalars_out

__all__ = ["phase2_pallas", "phase3_pallas"]


def _phase2_kernel(alpha_ref, r_ref, ap_ref, m_ref, rnew_ref, s_ref,
                   accrr_ref, accrz_ref):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        accrr_ref[...] = jnp.zeros_like(accrr_ref)
        accrz_ref[...] = jnp.zeros_like(accrz_ref)

    alpha = alpha_ref[0, 0]
    r_new = r_ref[0] - alpha * ap_ref[0]     # M4
    rnew_ref[...] = r_new[None]              # single store of record
    z = r_new / m_ref[0]                     # M5 — never leaves VMEM
    accrr_ref[...] += r_new * r_new          # M8 (hoisted)
    accrz_ref[...] += r_new * z              # M6

    @pl.when(i == pl.num_programs(0) - 1)
    def _reduce():
        _emit(s_ref, 0, jnp.sum(accrr_ref[...]))
        _emit(s_ref, 1, jnp.sum(accrz_ref[...]))


@functools.partial(jax.jit, static_argnames=("interpret",))
def phase2_pallas(alpha: jax.Array, r: jax.Array, ap: jax.Array,
                  diag: jax.Array, *, interpret: bool = False):
    """Fused phase 2.  Returns (r_new [n], scalars [rr, rz])."""
    rows, lanes = DOT_BLOCK
    n = r.shape[0]
    dt = r.dtype
    rp = _pad2d(r, dt)
    app = _pad2d(ap, dt)
    # pad M with ones: padded lanes compute z = 0/1 = 0, contributing 0.
    chunk = rows * lanes
    nb = rp.shape[0]
    mp = jnp.ones(nb * chunk, dt).at[:n].set(diag.astype(dt)).reshape(
        nb, rows, lanes)
    a2 = jnp.asarray(alpha, dt).reshape(1, 1)
    s_spec, s_shape = _scalars_out(2, dt)

    r_new, s = pl.pallas_call(
        _phase2_kernel,
        grid=(nb,),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM),
                  pl.BlockSpec((1, rows, lanes), lambda i: (i, 0, 0)),
                  pl.BlockSpec((1, rows, lanes), lambda i: (i, 0, 0)),
                  pl.BlockSpec((1, rows, lanes), lambda i: (i, 0, 0))],
        out_specs=[pl.BlockSpec((1, rows, lanes), lambda i: (i, 0, 0)),
                   s_spec],
        out_shape=[jax.ShapeDtypeStruct((nb, rows, lanes), dt), s_shape],
        scratch_shapes=[pltpu.VMEM((rows, lanes), dt)] * 2,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="phase2",
    )(a2, rp, app, mp)
    return r_new.reshape(-1)[:n], s[:, 0, 0]


def _phase3_kernel(ab_ref, rnew_ref, m_ref, p_ref, x_ref, pnew_ref, xnew_ref):
    alpha = ab_ref[0, 0]
    beta = ab_ref[0, 1]
    p = p_ref[0]                              # ONE read, two consumers
    z = rnew_ref[0] / m_ref[0]                # M5 recomputed (§5.3)
    pnew_ref[...] = (z + beta * p)[None]      # M7
    xnew_ref[...] = (x_ref[0] + alpha * p)[None]   # M3


@functools.partial(jax.jit, static_argnames=("interpret",))
def phase3_pallas(alpha: jax.Array, beta: jax.Array, r_new: jax.Array,
                  diag: jax.Array, p: jax.Array, x: jax.Array, *,
                  interpret: bool = False):
    """Fused phase 3.  Returns (p_new [n], x_new [n])."""
    rows, lanes = DOT_BLOCK
    n = r_new.shape[0]
    dt = r_new.dtype
    rp = _pad2d(r_new, dt)
    pp = _pad2d(p, dt)
    xp = _pad2d(x, dt)
    chunk = rows * lanes
    nb = rp.shape[0]
    mp = jnp.ones(nb * chunk, dt).at[:n].set(diag.astype(dt)).reshape(
        nb, rows, lanes)
    ab = jnp.stack([jnp.asarray(alpha, dt),
                    jnp.asarray(beta, dt)]).reshape(1, 2)

    p_new, x_new = pl.pallas_call(
        _phase3_kernel,
        grid=(nb,),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM),
                  pl.BlockSpec((1, rows, lanes), lambda i: (i, 0, 0)),
                  pl.BlockSpec((1, rows, lanes), lambda i: (i, 0, 0)),
                  pl.BlockSpec((1, rows, lanes), lambda i: (i, 0, 0)),
                  pl.BlockSpec((1, rows, lanes), lambda i: (i, 0, 0))],
        out_specs=[pl.BlockSpec((1, rows, lanes), lambda i: (i, 0, 0)),
                   pl.BlockSpec((1, rows, lanes), lambda i: (i, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct((nb, rows, lanes), dt),
                   jax.ShapeDtypeStruct((nb, rows, lanes), dt)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="phase3",
    )(ab, rp, mp, pp, xp)
    return p_new.reshape(-1)[:n], x_new.reshape(-1)[:n]
