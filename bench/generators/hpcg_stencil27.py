"""HPCG's operator: the 27-point stencil, and its right-hand sides.

The matrix has no randomness: an ``nx x ny x nz`` grid, diagonal 26,
every neighbour -1, rows at the boundary keeping only the neighbours
inside the grid.  A right-hand side is ``b = A x*`` with ``x*`` uniform
on the configuration's ``[low, high)`` (HPCG's all-ones, perturbed).
"""
from __future__ import annotations

import numpy as np
import scipy.sparse as sp

from bench import systems as S

#: the configuration's overrides that make the operator small enough for
#: the CPU tests (a 512-row grid)
SMALL = {"params": {"nx": 8, "ny": 8, "nz": 8}}


def hpcg_stencil27(nx: int, ny: int, nz: int) -> sp.csr_matrix:
    """HPCG's 27-point operator on an ``nx x ny x nz`` grid.

    Row ``i = x + nx*(y + ny*z)``.  Offsets are visited in (dz, dy, dx)
    order, so each row's columns come out sorted without a sort.
    """
    n = nx * ny * nz
    z, y, x = np.meshgrid(np.arange(nz), np.arange(ny), np.arange(nx),
                          indexing="ij")
    x, y, z = x.ravel(), y.ravel(), z.ravel()
    row = np.arange(n, dtype=np.int64)
    cols = np.empty((n, 27), np.int32)
    keep = np.empty((n, 27), bool)
    vals = np.empty((n, 27), np.float64)
    k = 0
    for dz in (-1, 0, 1):
        for dy in (-1, 0, 1):
            for dx in (-1, 0, 1):
                ok = ((x + dx >= 0) & (x + dx < nx) & (y + dy >= 0)
                      & (y + dy < ny) & (z + dz >= 0) & (z + dz < nz))
                cols[:, k] = row + dx + nx * (dy + ny * dz)
                keep[:, k] = ok
                vals[:, k] = 26.0 if (dx, dy, dz) == (0, 0, 0) else -1.0
                k += 1
    indptr = np.zeros(n + 1, np.int64)
    np.cumsum(keep.sum(axis=1), out=indptr[1:])
    return sp.csr_matrix((vals[keep], cols[keep], indptr), shape=(n, n))


def build(cfg: dict) -> sp.csr_matrix:
    p = cfg["params"]
    return hpcg_stencil27(p["nx"], p["ny"], p["nz"])


def rhs(cfg: dict, a: sp.csr_matrix, seed: int, j: int) -> np.ndarray:
    """Right-hand side ``j`` of a run: ``A x*``, ``x*`` from the seed."""
    lo, hi = cfg["rhs"]["low"], cfg["rhs"]["high"]
    return a @ S.rng_for(seed, S.STREAM_RHS, j).uniform(lo, hi, a.shape[0])
