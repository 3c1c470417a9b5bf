"""Mixed-precision sparse matvecs — the M1 module as Pallas kernels.

TPU adaptation of the paper's Serpens-based SpMV (§6, Fig. 8):

  ==============================  =========================================
  Callipepla (U280)               this kernel (TPU v5e)
  ==============================  =========================================
  16 HBM channels × 8 PEs         grid dimensions over systems and row
                                  blocks (``dimension_semantics="parallel"``)
  BRAM X-memory (4K deep)         x col-tile resident in VMEM; fetched by
                                  the BlockSpec ``index_map`` driven by the
                                  scalar-prefetched ``tile_cols`` stream —
                                  the Type-III memory-instruction analogue
  URAM Y-memory (24K deep)        y row-block accumulator in VMEM, revision
                                  over the slab grid dimension, written once
  64-bit packed nonzero           slot-major ELLPACK entry: value at
  (14b col, 18b row, fp32 val)    ``matrix_dtype`` + *local* col index;
                                  the row is the lane id
  FP32→FP64 cast + FMA            ``vals.astype(acc) * x.astype(acc)`` —
                                  the Mix-V3 cast happens in-register
  ==============================  =========================================

The gather ``x[col]`` is the one operation Mosaic does not offer in
general: its dynamic gather (``tpu.dynamic_gather``) permutes lanes
*within one 128-lane vreg*.  So x is held in VMEM as rows of 128 lanes
and :func:`_gather` walks it one row (chunk) at a time — broadcast the
chunk over the index block, lane-gather with ``col % 128``, keep the
lanes whose ``col // 128`` names that chunk.  That costs one
gather+select per chunk: ``col_tile / 128`` chunks for the ELLPACK
kernel, ``n_pad / 128`` for the SELL kernel, whose columns span the
whole vector.  Values are exact copies, so results are bit-identical
to a plain ``x[col]``.

Mosaic's block rule — the last two block dimensions divisible by
(8, 128) or equal to the array's — is met by squeezing (``None``) every
per-grid-step index dimension and by giving each 1-row output block a
unit axis of its own.  Index blocks are 32-bit (the gather needs
indices and table of one bit width); x is gathered at 32 bits and cast
to ``spmv_in_dtype`` values exactly.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.precision import PrecisionScheme

__all__ = ["spmv_pallas", "spmv_pallas_batched", "spmv_pallas_sell"]

#: lanes of one vreg — the span of one Mosaic dynamic gather.
LANES = 128
_LANE_BITS = LANES.bit_length() - 1

#: v5e has 128 MiB of VMEM and scopes 16 MiB to a kernel by default; a
#: SELL launch asks for what its double-buffered blocks need, up to this.
_VMEM_CAP = 100 << 20


def _gather_dtype(dt):
    """x is gathered at 32 bits (exact upcast of a 16-bit ``spmv_in``)."""
    return jnp.float32 if jnp.dtype(dt).itemsize < 4 else dt


def _x_chunks(x: jax.Array, dtype) -> jax.Array:
    """``[..., n] -> [..., ceil(n/128), 128]`` at ``dtype``, zero-padded:
    the chunked VMEM view :func:`_gather` reads."""
    n = x.shape[-1]
    nc = -(-n // LANES)
    x = x.astype(dtype)
    if nc * LANES != n:
        x = jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, nc * LANES - n)])
    return x.reshape(*x.shape[:-1], nc, LANES)


#: ``take_along_axis(row, lo, axis=1)`` for 2-D operands, spelled as the
#: one gather Mosaic lowers (``tpu.dynamic_gather`` along lanes) with
#: int32 indices whether or not x64 is on.
_LANE_TAKE = jax.lax.GatherDimensionNumbers(
    offset_dims=(), collapsed_slice_dims=(1,), start_index_map=(1,),
    operand_batching_dims=(0,), start_indices_batching_dims=(0,))


def _lane_take(row: jax.Array, lo: jax.Array) -> jax.Array:
    return jax.lax.gather(row, lo[..., None], _LANE_TAKE, slice_sizes=(1, 1),
                          mode=jax.lax.GatherScatterMode.PROMISE_IN_BOUNDS)


def _gather(x_ref, idx: jax.Array) -> jax.Array:
    """``x[idx]`` for an int32 index block ``idx [k, P]`` (P ≤ 128)
    against the chunked vector ``x_ref [nc, 128]``.

    Index ``j`` lives at ``x_ref[j // 128, j % 128]``.  Each chunk is
    broadcast over the ``k`` index rows, lane-gathered with
    ``j % 128`` and kept where ``j // 128`` names it (indices out of
    range gather 0).  Short tables unroll; long ones loop."""
    nc = x_ref.shape[0]
    k, p = idx.shape
    hi = idx >> _LANE_BITS
    lo = idx & (LANES - 1)

    def chunk(c, acc):
        row = jnp.broadcast_to(x_ref[pl.ds(c, 1), :], (k, LANES))
        return jnp.where(hi == c, _lane_take(row, lo), acc)

    acc = jnp.zeros((k, p), x_ref.dtype)
    if nc <= 8:
        for c in range(nc):
            acc = chunk(c, acc)
        return acc
    return jax.lax.fori_loop(0, nc, chunk, acc)


def _spmv_kernel(tile_cols_ref, vals_ref, lcols_ref, x_ref, y_ref, *,
                 acc_dtype, in_dtype):
    """One (row-block i, slab t) grid step of one system:
    ``y[i] += Σ_e vals[e, :] ⊙ x_tile[lcols[e, :]]``."""
    t = pl.program_id(1)

    @pl.when(t == 0)
    def _init():
        y_ref[...] = jnp.zeros_like(y_ref)

    rows = vals_ref.shape[-1]
    p = min(rows, LANES)
    for j in range(rows // p):             # one 128-lane piece at a time
        sl = pl.ds(j * p, p)
        xg = _gather(x_ref, lcols_ref[:, sl]).astype(in_dtype)
        prod = vals_ref[:, sl].astype(acc_dtype) * xg.astype(acc_dtype)
        y_ref[:, sl] += jnp.sum(prod, axis=0, keepdims=True)


@functools.partial(jax.jit, static_argnames=("scheme", "interpret"))
def spmv_pallas_batched(tile_cols: jax.Array, vals: jax.Array,
                        local_cols: jax.Array, x_tiles: jax.Array, *,
                        scheme: PrecisionScheme,
                        interpret: bool = False) -> jax.Array:
    """Batch-of-systems banked-ELLPACK SpMV — G independent A·x.

    tile_cols int32[G, B, T] — per-system memory-instruction streams;
    vals scheme.matrix_dtype[G, B, T, E, R]; local_cols int32[G, B, T,
    E, R]; x_tiles [G, n_col_tiles, C] (cast to ``scheme.spmv_in_dtype``
    here — the Mix-V1/V2 information loss point).  Returns
    acc_dtype[G, B, R].  On the chip R must be a multiple of 128.

    One launch per system: each scalar-prefetches only its own
    ``tile_cols`` row, flattened, because SMEM (1 MiB on v5e) pads a
    2-D array's last axis to 128 words and cannot hold the whole batch
    at chip sizes.  Every launch reads the full stacked operands at its
    own fixed ``g`` through the index maps, so no operand is sliced or
    copied.
    """
    G, B, T, E, R = vals.shape
    if R > LANES and R % LANES:
        raise ValueError(f"block_rows={R} must be ≤ 128 or a multiple "
                         "of 128")
    acc = scheme.spmv_acc_dtype
    x_in = x_tiles.astype(scheme.spmv_in_dtype)
    xc = _x_chunks(x_in, _gather_dtype(x_in.dtype))   # [G, nct, nc, 128]
    nc = xc.shape[2]
    local_cols = local_cols.astype(jnp.int32)
    kernel = functools.partial(_spmv_kernel, acc_dtype=acc,
                               in_dtype=scheme.spmv_in_dtype)

    def one_system(g):
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B, T),
            in_specs=[
                pl.BlockSpec((None, None, None, E, R),
                             lambda i, t, tc: (g, i, t, 0, 0)),
                pl.BlockSpec((None, None, None, E, R),
                             lambda i, t, tc: (g, i, t, 0, 0)),
                pl.BlockSpec((None, None, nc, LANES),
                             lambda i, t, tc: (g, tc[i * T + t], 0, 0)),
            ],
            out_specs=pl.BlockSpec((None, 1, R), lambda i, t, tc: (i, 0, 0)),
        )
        return pl.pallas_call(
            kernel,
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((B, 1, R), acc),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "arbitrary")),
            interpret=interpret,
            name="spmv_ellpack",
        )(tile_cols[g].reshape(-1), vals, local_cols, xc)

    return jnp.stack([one_system(g) for g in range(G)]).reshape(G, B, R)


def spmv_pallas(tile_cols: jax.Array, vals: jax.Array, local_cols: jax.Array,
                x_tiles: jax.Array, *, scheme: PrecisionScheme,
                interpret: bool = False) -> jax.Array:
    """Single-system banked-ELLPACK SpMV: the G = 1 batched kernel.

    tile_cols int32[B, T]; vals scheme.matrix_dtype[B, T, E, R];
    local_cols int32[B, T, E, R]; x_tiles [n_col_tiles, C].
    Returns acc_dtype[B, R].
    """
    return spmv_pallas_batched(tile_cols[None], vals[None],
                               local_cols[None], x_tiles[None],
                               scheme=scheme, interpret=interpret)[0]


def _spmv_sell_kernel(cols_ref, vals_ref, x_ref, y_ref, *, acc_dtype,
                      in_dtype):
    """One (system g, 128-row block) grid step of one SELL width group:
    y_sorted[rows] = tree-sum over the group's w slots of vals ⊙ x[cols]."""
    from repro.core.batch import rounded_products, tree_sum
    c = cols_ref[...].astype(jnp.int32)     # [w, P]
    xg = _gather(x_ref, c).astype(in_dtype)
    prod = rounded_products(vals_ref[...], xg, acc_dtype)
    y_ref[...] = tree_sum(prod, axis=0)[None, :]


@functools.partial(jax.jit, static_argnames=("groups", "scheme",
                                             "interpret"))
def spmv_pallas_sell(cols: jax.Array, vals: jax.Array, x: jax.Array, *,
                     groups, scheme: PrecisionScheme,
                     interpret: bool = False) -> jax.Array:
    """Batched SELL-C-σ SpMV — one Pallas launch per static width group.

    ``cols/vals`` are the flat slot-major ``[G, L]`` arrays of
    :func:`repro.sparse.stacking.stack_sell` (values at the scheme's
    at-rest ``matrix_dtype``, indices int16/int32), ``x`` is
    ``[G, n_pad]``, ``groups`` the static ``(rows, width)`` signature.
    Each group is a dense ``[w, rows]`` rectangle, padded here to whole
    128-row blocks (the gather needs whole vregs of indices), whose row
    reduction is the same deterministic halving tree as the XLA path
    (:func:`repro.core.batch.tree_sum`), so the result is bit-identical
    to :func:`repro.core.batch.batched_matvec_sell` before the
    un-permutation.  x stays resident in VMEM for all of a system's row
    blocks.

    Returns ``acc_dtype[G, n_pad]`` in **sorted** row order — the caller
    applies the stacked ``iperm`` (and the vector-dtype cast).
    """
    G, n_pad = x.shape
    acc = scheme.spmv_acc_dtype
    x_in = x.astype(scheme.spmv_in_dtype)
    xc = _x_chunks(x_in, _gather_dtype(x_in.dtype))   # [G, nc, 128]
    nc = xc.shape[1]
    parts, off = [], 0
    for rows, w in groups:
        if w == 0:
            parts.append(jnp.zeros((G, rows), acc))
            continue
        p = LANES
        rows_p = -(-rows // p) * p
        # double-buffered index, value and x blocks, plus as much again
        # for the kernel's [w, 128] temporaries
        blocks = 2 * (w * p * (cols.dtype.itemsize + vals.dtype.itemsize)
                      + nc * LANES * xc.dtype.itemsize)
        vmem = min(_VMEM_CAP, max(16 << 20, 2 * blocks))
        pad = ((0, 0), (0, 0), (0, rows_p - rows))
        c = jnp.pad(cols[:, off:off + rows * w].reshape(G, w, rows), pad)
        v = jnp.pad(vals[:, off:off + rows * w].reshape(G, w, rows), pad)
        y = pl.pallas_call(
            functools.partial(_spmv_sell_kernel, acc_dtype=acc,
                              in_dtype=scheme.spmv_in_dtype),
            grid=(G, rows_p // p),
            in_specs=[
                pl.BlockSpec((None, w, p), lambda g, i: (g, 0, i)),
                pl.BlockSpec((None, w, p), lambda g, i: (g, 0, i)),
                pl.BlockSpec((None, nc, LANES), lambda g, i: (g, 0, 0)),
            ],
            out_specs=pl.BlockSpec((None, 1, p), lambda g, i: (g, 0, i)),
            out_shape=jax.ShapeDtypeStruct((G, 1, rows_p), acc),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel"),
                vmem_limit_bytes=vmem),
            interpret=interpret,
            name="spmv_sell",
        )(c, v, xc)
        parts.append(y[:, 0, :rows])
        off += rows * w
    return jnp.concatenate(parts, axis=1)
