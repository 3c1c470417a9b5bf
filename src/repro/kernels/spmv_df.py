"""The double-fp32 banked-ELLPACK SpMV: a bf16 matrix times a wide vector.

:func:`spmv_pallas_df_batched` is :func:`repro.kernels.spmv.spmv_pallas_batched`
for the ``tpu_v3_df`` scheme (:mod:`repro.core.dfloat`): the same
operands, tiling, grid and scalar-prefetched tile stream, but x comes
as K fp32 words (2 for a search direction, 3 for the solution) and y
goes out as a pair.  The matrix stream is unchanged, 2 B a value and a
local index; only the x tiles and y are wider.

Per entry, each gathered word of x is cut into two halves of at most 12
significant bits (:func:`repro.core.dfloat.split`); a bf16 value has 8,
so each of the 2K products is exact in fp32, and
:func:`repro.core.dfloat.two_sum` of a word's two products is the
entry's exact share of that word.  Each word keeps a pair accumulator
of its own per row, across slots and slabs: a word's terms share its
scale, so a row whose terms cancel (a grid Laplacian's 4·x_i − Σ x_j,
with x near 1e6 and the result near 1) keeps every bit, where one
accumulator for all words would round the small words' terms at the
large word's scale (1e-8 of 1e6 here, 1e-8 of ``||b||`` in all).  The
K row sums are added outside the kernel, smallest word first.

x is laid out ``[G, n_col_tiles, K·nc, 128]``: word k of a tile is
chunk rows ``[k·nc, (k+1)·nc)``, gathered with the same in-vreg lane
gather as the fp32 kernel.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.dfloat import pair_add, split, two_sum
from repro.kernels.spmv import LANES, _LANE_BITS, _lane_take

__all__ = ["spmv_pallas_df_batched"]


def _gather_word(x_ref, idx, base: int, nc: int):
    """``x[idx]`` of the word whose chunks start at row ``base`` of
    ``x_ref [K·nc, 128]``: :func:`repro.kernels.spmv._gather` over that
    word's ``nc`` chunks only."""
    k, p = idx.shape
    hi = idx >> _LANE_BITS
    lo = idx & (LANES - 1)
    acc = jnp.zeros((k, p), x_ref.dtype)
    for c in range(nc):
        row = jnp.broadcast_to(x_ref[pl.ds(base + c, 1), :], (k, LANES))
        acc = jnp.where(hi == c, _lane_take(row, lo), acc)
    return acc


def _fold_rows(h, l):
    """Sum a pair's rows ``[E, P] -> [1, P]``: halving while the count is
    even (E is a power of two when stacked with buckets), then row by
    row."""
    while h.shape[0] % 2 == 0:
        m = h.shape[0] // 2
        h, l = pair_add(h[:m], l[:m], h[m:], l[m:])
    out = h[0:1], l[0:1]
    for e in range(1, h.shape[0]):
        out = pair_add(*out, h[e:e + 1], l[e:e + 1])
    return out


def _spmv_df_kernel(tile_cols_ref, vals_ref, lcols_ref, x_ref, yh_ref,
                    yl_ref, *, words: int):
    """One (row-block i, slab t) grid step of one system: for each word
    k of x, ``y_k[i] += Σ_e vals[e, :] ⊙ x_k[lcols[e, :]]``, y_k a pair
    (row k of the ``[K, R]`` output blocks)."""
    t = pl.program_id(1)

    @pl.when(t == 0)
    def _init():
        yh_ref[...] = jnp.zeros_like(yh_ref)
        yl_ref[...] = jnp.zeros_like(yl_ref)

    rows = vals_ref.shape[-1]
    nc = x_ref.shape[0] // words
    p = min(rows, LANES)
    for j in range(rows // p):             # one 128-lane piece at a time
        sl = pl.ds(j * p, p)
        idx = lcols_ref[:, sl]
        a = vals_ref[:, sl].astype(jnp.float32)          # exact
        for k in range(words):
            hi, lo = split(_gather_word(x_ref, idx, k * nc, nc))
            h, l = _fold_rows(*two_sum(a * hi, a * lo))  # exact products
            h, l = pair_add(yh_ref[k:k + 1, sl], yl_ref[k:k + 1, sl], h, l)
            yh_ref[k:k + 1, sl] = h
            yl_ref[k:k + 1, sl] = l


def x_words_tiles(words, n_col_tiles: int, col_tile: int) -> jax.Array:
    """``[K]`` words of ``[G, n]`` -> the kernel's x operand
    ``[G, n_col_tiles, K·nc, 128]`` (zero-padded)."""
    x = jnp.stack(words)                                  # [K, G, n]
    K, G, n = x.shape
    padded = n_col_tiles * col_tile
    k = min(n, padded)
    x = jnp.zeros((K, G, padded), jnp.float32).at[:, :, :k].set(x[:, :, :k])
    nc = col_tile // LANES
    x = x.reshape(K, G, n_col_tiles, nc, LANES).transpose(1, 2, 0, 3, 4)
    return x.reshape(G, n_col_tiles, K * nc, LANES)


@functools.partial(jax.jit, static_argnames=("words", "interpret"))
def spmv_pallas_df_batched(tile_cols: jax.Array, vals: jax.Array,
                           local_cols: jax.Array, x_tiles: jax.Array, *,
                           words: int, interpret: bool = False):
    """G independent ``A·x`` with x of ``words`` fp32 words.

    Operands as :func:`repro.kernels.spmv.spmv_pallas_batched` takes
    them (``vals`` bf16), except ``x_tiles`` from :func:`x_words_tiles`.
    Returns ``(y_hi, y_lo)``, each ``float32[G, B, R]``: the words' row
    sums added, smallest first.  One launch per system, named
    ``spmv_ellpack_df``.
    """
    G, B, T, E, R = vals.shape
    if R > LANES and R % LANES:
        raise ValueError(f"block_rows={R} must be ≤ 128 or a multiple "
                         "of 128")
    rows_x = x_tiles.shape[2]
    local_cols = local_cols.astype(jnp.int32)
    kernel = functools.partial(_spmv_df_kernel, words=words)
    out = jax.ShapeDtypeStruct((B, words, R), jnp.float32)
    out_spec = pl.BlockSpec((None, words, R), lambda i, t, tc: (i, 0, 0))

    def one_system(g):
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B, T),
            in_specs=[
                pl.BlockSpec((None, None, None, E, R),
                             lambda i, t, tc: (g, i, t, 0, 0)),
                pl.BlockSpec((None, None, None, E, R),
                             lambda i, t, tc: (g, i, t, 0, 0)),
                pl.BlockSpec((None, None, rows_x, LANES),
                             lambda i, t, tc: (g, tc[i * T + t], 0, 0)),
            ],
            out_specs=[out_spec, out_spec],
        )
        return pl.pallas_call(
            kernel,
            grid_spec=grid_spec,
            out_shape=[out, out],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "arbitrary")),
            interpret=interpret,
            name="spmv_ellpack_df",
        )(tile_cols[g].reshape(-1), vals, local_cols, x_tiles)

    ys = [one_system(g) for g in range(G)]
    yh, yl = (jnp.stack([y[w] for y in ys]) for w in range(2))  # [G,B,K,R]
    h, l = yh[:, :, words - 1], yl[:, :, words - 1]
    for k in range(words - 2, -1, -1):
        h, l = pair_add(h, l, yh[:, :, k], yl[:, :, k])
    return h.reshape(G, B, R), l.reshape(G, B, R)
