"""Dot-product kernels — the M2/M6/M8 modules, and the pipelined-CG dot3.

Paper §4.2 footnote 1: the FPGA dot modules run two phases — Phase I
multiply-accumulates into a *cyclic delay buffer* at II=1 (the FP-add
latency L=5 is hidden by L independent partial sums), Phase II collapses
the buffer with a fixed 5·L-cycle pass.

The TPU spelling of the same idea: Phase I accumulates an ``[8, LANES]``
VMEM tile of partial sums — every VPU lane owns one partial, so the serial
FP-add dependence is broken exactly as the delay buffer breaks it — and
Phase II is a log-depth tree reduction of the tile on the final grid step.
Mosaic stores no scalar to VMEM, so each result leaves the kernel
broadcast over one ``(8, 128)`` tile of the output (:func:`_emit`).

``dot3`` fuses the three reductions of pipelined CG (γ = r·u, δ = w·u,
‖r‖²) into ONE sweep: r, u, w stream through VMEM once and three
accumulator tiles update per step.  At pod scale this is what turns three
all-reduces into one (see repro/core/pipelined.py).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["dot_pallas", "dot3_pallas", "DOT_BLOCK"]

#: rows × lanes of one grid-step tile (8 sublanes × 512 lanes of fp32).
DOT_BLOCK = (8, 512)

#: one vreg of fp32 — the output tile each scalar result is broadcast over.
OUT_TILE = (8, 128)


def _pad2d(v: jax.Array, dtype) -> jax.Array:
    """Zero-pad a vector to [nb, 8, L] grid-of-tiles layout."""
    rows, lanes = DOT_BLOCK
    chunk = rows * lanes
    n = v.shape[0]
    nb = max(1, -(-n // chunk))
    vp = jnp.zeros(nb * chunk, dtype).at[:n].set(v.astype(dtype))
    return vp.reshape(nb, rows, lanes)


def _emit(o_ref, k: int, value) -> None:
    """Write scalar result ``k`` as a whole ``OUT_TILE`` of ``o_ref``."""
    o_ref[k] = jnp.broadcast_to(value, OUT_TILE).astype(o_ref.dtype)


def _scalars_out(k: int, dtype):
    """Block spec and shape of ``k`` scalar results, one ``OUT_TILE``
    each; the caller reads them at ``[:, 0, 0]``."""
    return (pl.BlockSpec((k,) + OUT_TILE, lambda i: (0, 0, 0)),
            jax.ShapeDtypeStruct((k,) + OUT_TILE, dtype))


def _dot_kernel(a_ref, b_ref, o_ref, acc_ref):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += a_ref[0] * b_ref[0]          # Phase I: lane partials

    @pl.when(i == pl.num_programs(0) - 1)
    def _reduce():                               # Phase II: tree reduce
        _emit(o_ref, 0, jnp.sum(acc_ref[...]))


@functools.partial(jax.jit, static_argnames=("acc_dtype", "interpret"))
def dot_pallas(a: jax.Array, b: jax.Array, *, acc_dtype=jnp.float32,
               interpret: bool = False) -> jax.Array:
    """⟨a, b⟩ with lane-parallel partial sums.  Returns a 0-d scalar."""
    rows, lanes = DOT_BLOCK
    ap = _pad2d(a, acc_dtype)
    bp = _pad2d(b, acc_dtype)
    nb = ap.shape[0]
    out_spec, out_shape = _scalars_out(1, acc_dtype)
    out = pl.pallas_call(
        _dot_kernel,
        grid=(nb,),
        in_specs=[pl.BlockSpec((1, rows, lanes), lambda i: (i, 0, 0)),
                  pl.BlockSpec((1, rows, lanes), lambda i: (i, 0, 0))],
        out_specs=out_spec,
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((rows, lanes), acc_dtype)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="dot",
    )(ap, bp)
    return out[0, 0, 0]


def _dot3_kernel(r_ref, u_ref, w_ref, o_ref, accru_ref, accwu_ref, accrr_ref):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        accru_ref[...] = jnp.zeros_like(accru_ref)
        accwu_ref[...] = jnp.zeros_like(accwu_ref)
        accrr_ref[...] = jnp.zeros_like(accrr_ref)

    r = r_ref[0]
    u = u_ref[0]
    w = w_ref[0]
    accru_ref[...] += r * u
    accwu_ref[...] += w * u
    accrr_ref[...] += r * r

    @pl.when(i == pl.num_programs(0) - 1)
    def _reduce():
        _emit(o_ref, 0, jnp.sum(accru_ref[...]))
        _emit(o_ref, 1, jnp.sum(accwu_ref[...]))
        _emit(o_ref, 2, jnp.sum(accrr_ref[...]))


@functools.partial(jax.jit, static_argnames=("acc_dtype", "interpret"))
def dot3_pallas(r: jax.Array, u: jax.Array, w: jax.Array, *,
                acc_dtype=jnp.float32, interpret: bool = False) -> jax.Array:
    """Fused [r·u, w·u, r·r] in one sweep over r, u, w.  Returns shape (3,)."""
    rows, lanes = DOT_BLOCK
    rp = _pad2d(r, acc_dtype)
    up = _pad2d(u, acc_dtype)
    wp = _pad2d(w, acc_dtype)
    nb = rp.shape[0]
    out_spec, out_shape = _scalars_out(3, acc_dtype)
    out = pl.pallas_call(
        _dot3_kernel,
        grid=(nb,),
        in_specs=[pl.BlockSpec((1, rows, lanes), lambda i: (i, 0, 0))] * 3,
        out_specs=out_spec,
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((rows, lanes), acc_dtype)] * 3,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="dot3",
    )(rp, up, wp)
    return out[:, 0, 0]
