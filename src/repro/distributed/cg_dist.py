"""Distributed JPCG — row-partitioned shard_map solver at pod scale.

Decomposition (DESIGN.md §5): rows of A block-partitioned over the
flattened mesh ("rows" = data × model [× pod]); every vector (r, p, x)
lives sharded by row.  Per iteration:

* **SpMV** — each shard holds a banked-ELL slice with *global* column
  tiles; ``all_gather`` assembles the x-window (stencil matrices could use
  a neighbor ``ppermute`` halo instead — ``halo_width`` in the partition
  metadata says when; all-gather is the general correct path and is what
  the roofline accounts).
* **dots** — local partial then ``psum``: the FPGA's scalar FIFO to the
  global controller becomes an ICI all-reduce.
* **paper schedule (vsr)** — two psums per iteration (α and β barriers),
  exactly Callipepla's two scalar barriers.
* **pipelined** — the beyond-paper variant: ONE psum of a packed
  length-4 vector per iteration ([γ, δ, ‖r‖², pap-guard]), overlapped
  with the next SpMV by XLA's scheduler.  At 512 chips the α/β reductions
  are latency-bound, so halving their count halves the collective term.

Termination stays on-the-fly: the while_loop predicate reads the psum'd
``rr`` — every shard sees the same scalar, so control flow is coherent
without a host round-trip (paper Challenge 1 at pod scale).
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.operators import bell_spmv_jnp
from repro.core.precision import PrecisionScheme, get_scheme
from repro.sparse.partition import PartitionedMatrix, partition_rows

__all__ = ["DistCG", "make_dist_solver"]

AXIS = "rows"


@dataclasses.dataclass(frozen=True)
class DistCG:
    """Compiled distributed solver bound to a mesh + partitioned matrix."""
    mesh: Mesh
    part: PartitionedMatrix
    scheme: PrecisionScheme
    method: str
    solve: callable            # (b, x0, diag) -> (x, iters, rr)


def _local_spmv(shard_args, x_full, *, block_rows, col_tile, scheme, n_pad):
    # shard_map keeps the sharded leading axis at local size 1 — drop it.
    tile_cols, vals, lrows, lcols = (a[0] for a in shard_args)
    if x_full.shape[0] >= n_pad:          # row padding exceeds col padding
        x_pad = x_full[:n_pad]
    else:
        x_pad = jnp.zeros(n_pad, x_full.dtype).at[: x_full.shape[0]].set(
            x_full)
    return bell_spmv_jnp(tile_cols, vals, lrows, lcols, x_pad,
                         block_rows=block_rows, col_tile=col_tile,
                         scheme=scheme)


def make_dist_solver(a, mesh: Mesh, *, scheme="mixed_v3",
                     method: str = "pipelined", tol: float = 1e-12,
                     maxiter: int = 20_000, block_rows: int = 256,
                     col_tile: int = 512, comm: str = "auto",
                     part: Optional[PartitionedMatrix] = None) -> DistCG:
    """Build a shard_map JPCG over ``mesh`` (all axes flattened to rows).

    ``comm``: how the SpMV assembles its x-window —
      * "allgather" — gather the full vector (general matrices);
      * "halo" — two neighbor ``ppermute``s of ``halo_pad`` entries
        (stencil matrices: bytes drop from (S−1)/S·n to 2·halo per
        device — ~500× for the 1M-row Poisson class);
      * "auto" — halo when the partition supports it and the halo is
        < ¼ of the shard, else allgather.
    """
    scheme = get_scheme(scheme)
    vd = scheme.vector_dtype
    n_shards = int(np.prod([mesh.shape[a] for a in mesh.axis_names]))
    if part is None:
        part = partition_rows(a, n_shards, block_rows=block_rows,
                              col_tile=col_tile)
    n = part.shape[0]
    rows_local = part.rows_per_shard
    n_pad = part.padded_cols
    axes = tuple(mesh.axis_names)

    if comm == "auto":
        comm = ("halo" if part.supports_halo
                and part.halo_pad * 4 <= rows_local else "allgather")
    use_halo = comm == "halo"
    if use_halo and not part.supports_halo:
        raise ValueError("partition does not support halo exchange "
                         f"(halo={part.halo_width}, R={rows_local})")
    halo_pad = part.halo_pad if use_halo else 0
    win_pad = rows_local + 2 * halo_pad        # x-window length (halo)

    shard_spec = P(axes)                       # leading shard axis
    vec_spec = P(axes)                         # row-sharded vectors
    rep = P()

    def _perm(shift):
        return [(i, i + shift) for i in range(n_shards)
                if 0 <= i + shift < n_shards]

    def spmv(shard_args, p_local):
        if use_halo:
            # one-hop halo exchange: left tail -> right neighbor, right
            # head -> left neighbor; edge shards receive zeros (ppermute
            # semantics), matching the absent boundary columns.
            left = jax.lax.ppermute(p_local[-halo_pad:], axes, _perm(1))
            right = jax.lax.ppermute(p_local[:halo_pad], axes, _perm(-1))
            window = jnp.concatenate([left, p_local, right])
            y = _local_spmv(shard_args,
                            window.astype(scheme.spmv_in_dtype),
                            block_rows=part.block_rows,
                            col_tile=part.col_tile, scheme=scheme,
                            n_pad=win_pad)
            return y[:rows_local].astype(vd)
        p_full = jax.lax.all_gather(p_local, axes, tiled=True)
        y = _local_spmv(shard_args, p_full.astype(scheme.spmv_in_dtype),
                        block_rows=part.block_rows, col_tile=part.col_tile,
                        scheme=scheme, n_pad=n_pad)
        return y[:rows_local].astype(vd)

    def pdot(u, v):
        return jax.lax.psum(jnp.dot(u, v), axes)

    # ---------------- paper-faithful (two reductions) ----------------
    def solve_vsr(shard_args, b_l, x_l, d_l):
        r = b_l - spmv(shard_args, x_l)
        z = r / d_l
        p = z
        rz = pdot(r, z)
        rr = pdot(r, r)
        st = (jnp.zeros((), jnp.int32), x_l, r, p, rz, rr)

        def cond(s):
            return (s[0] < maxiter) & (s[5] > tol)

        def body(s):
            i, x, r, p, rz, rr = s
            ap = spmv(shard_args, p)
            alpha = rz / pdot(p, ap)                 # reduction 1
            r2 = r - alpha * ap
            z = r2 / d_l
            packed = jnp.stack([jnp.dot(r2, r2), jnp.dot(r2, z)])
            packed = jax.lax.psum(packed, axes)      # reduction 2 (fused rr+rz)
            rr2, rz2 = packed[0], packed[1]
            beta = rz2 / rz
            return (i + 1, x + alpha * p, r2, z + beta * p, rz2, rr2)

        i, x, r, p, rz, rr = jax.lax.while_loop(cond, body, st)
        return x, i, rr

    # ---------------- pipelined (one reduction) -----------------------
    def solve_pipe(shard_args, b_l, x_l, d_l):
        r = b_l - spmv(shard_args, x_l)
        u = r / d_l
        w = spmv(shard_args, u)
        g0 = jax.lax.psum(
            jnp.stack([jnp.dot(r, u), jnp.dot(w, u), jnp.dot(r, r)]), axes)
        zero = jnp.zeros_like(r)
        one = jnp.ones((), vd)
        st = (jnp.zeros((), jnp.int32), x_l, r, u, w, zero, zero, zero,
              zero, g0[0], one, g0[1], one, g0[2])

        def cond(s):
            return (s[0] < maxiter) & (s[13] > tol)

        def body(s):
            (i, x, r, u, w, z, q, sv, p, gamma, gamma_prev, delta,
             alpha_prev, rr) = s
            m = w / d_l                          # M⁻¹ w
            nvec = spmv(shard_args, m)           # overlaps the psum below
            first = i == 0
            beta = jnp.where(first, jnp.zeros((), vd), gamma / gamma_prev)
            denom = delta - beta * gamma / jnp.where(first, one, alpha_prev)
            alpha = gamma / jnp.where(first, delta, denom)
            z2 = nvec + beta * z
            q2 = m + beta * q
            s2 = w + beta * sv
            p2 = u + beta * p
            x2 = x + alpha * p2
            r2 = r - alpha * s2
            u2 = u - alpha * q2
            w2 = w - alpha * z2
            g = jax.lax.psum(jnp.stack([jnp.dot(r2, u2), jnp.dot(w2, u2),
                                        jnp.dot(r2, r2)]), axes)  # THE psum
            return (i + 1, x2, r2, u2, w2, z2, q2, s2, p2,
                    g[0], gamma, g[1], alpha, g[2])

        out = jax.lax.while_loop(cond, body, st)
        return out[1], out[0], out[13]

    kern = solve_pipe if method == "pipelined" else solve_vsr
    shard_in = (shard_spec,) * 4

    mapped = jax.shard_map(
        kern, mesh=mesh,
        in_specs=(shard_in, vec_spec, vec_spec, vec_spec),
        out_specs=(vec_spec, rep, rep))

    n_rows_pad = part.padded_rows

    def _pad(v, fill):
        out = jnp.full(n_rows_pad, fill, vd)
        return out.at[: v.shape[0]].set(v.astype(vd))

    tile_cols_host = part.tile_cols_halo() if use_halo else part.tile_cols

    @jax.jit
    def solve(b, x0, diag):
        """b/x0/diag: global vectors of length n (padded here; diag pads
        with 1 so the padded rows solve the identity — no NaNs)."""
        shard_args = (jnp.asarray(tile_cols_host),
                      jnp.asarray(part.vals).astype(scheme.matrix_dtype),
                      jnp.asarray(part.local_rows),
                      jnp.asarray(part.local_cols))
        x, i, rr = mapped(shard_args, _pad(b, 0.0), _pad(x0, 0.0),
                          _pad(diag, 1.0))
        return x[:n], i, rr

    return DistCG(mesh=mesh, part=part, scheme=scheme, method=method,
                  solve=solve)
