"""ecology2's operator: a grounded 4-neighbour resistor grid, and its
right-hand sides.

SuiteSparse McRae/ecology2 is a Circuitscape landscape: a 1000 x 1000
grid of cells, each joined to its 4 neighbours by a conductance, with
one interior node tied to ground.  Its matrix is the grid's weighted
Laplacian with the ground node's row and column dropped: 999,999 rows,
4,995,991 nonzeros.  Here every conductance is 1 (exact in bfloat16):
off-diagonals -1, each diagonal the node's degree in the full grid
(2, 3 or 4; a neighbour of the ground node keeps the edge to it).  The
matrix has no randomness.

A right-hand side is the paper's b = 1, perturbed: uniform on the
configuration's ``[low, high)`` from the run's seed, one current per
node.
"""
from __future__ import annotations

import numpy as np
import scipy.sparse as sp

from bench import systems as S

#: the configuration's overrides that make the operator small enough for
#: the CPU tests (a 32 x 32 grid, grounded at (16, 16): 1023 rows)
SMALL = {"params": {"nx": 32, "ny": 32, "ground": [16, 16]}}


def grounded_grid(nx: int, ny: int, ground) -> sp.csr_matrix:
    """The unit-conductance Laplacian of an ``nx x ny`` 4-neighbour grid,
    node ``(gx, gy)`` grounded (its row and column dropped).

    Node ``(x, y)`` is index ``x + nx * y`` before the drop."""
    def path(n):
        return sp.diags([np.ones(n - 1), np.ones(n - 1)], [-1, 1])
    adj = (sp.kron(sp.identity(ny), path(nx))
           + sp.kron(path(ny), sp.identity(nx))).tocsr()
    deg = np.asarray(adj.sum(axis=1)).ravel()
    lap = (sp.diags(deg) - adj).tocsr()
    g = int(ground[0]) + nx * int(ground[1])
    keep = np.delete(np.arange(nx * ny), g)
    a = lap[keep][:, keep].tocsr()
    a.sort_indices()
    return a.astype(np.float64)


def build(cfg: dict) -> sp.csr_matrix:
    p = cfg["params"]
    a = grounded_grid(p["nx"], p["ny"], p["ground"])
    want = cfg.get("rows"), cfg.get("nnz")
    if (p["nx"], p["ny"]) == (1000, 1000):
        assert a.shape[0] == want[0] == 999_999, a.shape
        assert a.nnz == want[1] == 4_995_991, a.nnz
    return a


def rhs(cfg: dict, a: sp.csr_matrix, seed: int, j: int) -> np.ndarray:
    """Right-hand side ``j`` of a run: currents uniform on ``[low, high)``."""
    lo, hi = cfg["rhs"]["low"], cfg["rhs"]["high"]
    return S.rng_for(seed, S.STREAM_RHS, j).uniform(lo, hi, a.shape[0])
