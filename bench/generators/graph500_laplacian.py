"""A Graph500 Kronecker graph's Laplacian, solved from its search keys.

The operator is the Laplacian of one Kronecker graph, made strictly
diagonally dominant: off-diagonals -1, diagonal ``dominance x degree``
rounded up to the next value bfloat16 holds (so A is exact at rest in a
bfloat16 matrix stream), and 1 on the rows of isolated vertices.  The
graph comes from the configuration's ``matrix_seed``, so every run packs
the same shapes.

Graph500 runs its kernel from ``search_keys`` (64) vertices of degree at
least 1, drawn at random.  Here they are drawn once from the
``matrix_seed``, and a request from key ``v`` solves ``A x = e_v``: a
personalised PageRank from ``v`` with restart ``1 - 1/dominance``.  A
run's seed orders the keys: right-hand side ``j`` takes key
``perm_c[j mod 64]`` with ``perm_c`` drawn for cycle ``c = j div 64``,
so every seed solves each key once per cycle.
"""
from __future__ import annotations

import numpy as np
import scipy.sparse as sp

from bench import systems as S

#: the configuration's overrides that make the operator small enough for
#: the CPU tests (SCALE 8: 256 vertices, the 64 search keys still fit)
SMALL = {"params": {"scale": 8}}


def bf16_ceil(v) -> np.ndarray:
    """Smallest bfloat16 value >= each of ``v`` (positive, finite),
    returned as float64.  bfloat16 keeps 8 significant bits."""
    v = np.asarray(v, np.float64)
    m, e = np.frexp(v)                       # v = m * 2**e, m in [0.5, 1)
    return np.ldexp(np.ceil(m * 256.0) / 256.0, e)


def kronecker_edges(scale: int, edgefactor: int, initiator,
                    rng: np.random.Generator) -> np.ndarray:
    """Graph500's Kronecker edge list, ``int64[2, edgefactor * 2**scale]``.

    Follows the specification's generator: per bit, the row bit is set
    with probability ``C + D`` and the column bit with ``B / (A + B)``
    or ``D / (C + D)`` by the row bit; then the vertex labels are
    permuted at random.  (The specification's final shuffle of the edge
    order does not change the graph, and is left out.)
    """
    a, b, c, _ = (float(p) for p in initiator)
    n, m = 1 << scale, edgefactor << scale
    ab, c_norm, a_norm = a + b, c / (1.0 - (a + b)), a / (a + b)
    ij = np.zeros((2, m), np.int64)
    for bit in range(scale):
        ii = rng.random(m) > ab
        jj = rng.random(m) > np.where(ii, c_norm, a_norm)
        ij[0] += ii.astype(np.int64) << bit
        ij[1] += jj.astype(np.int64) << bit
    return rng.permutation(n)[ij]


def graph500_laplacian(scale: int, edgefactor: int, initiator,
                       dominance: float,
                       rng: np.random.Generator) -> sp.csr_matrix:
    """Strictly diagonally dominant Laplacian of one Kronecker graph.

    The graph is made undirected; self-loops and repeated edges are
    dropped.  Off-diagonals are -1 and the diagonal is
    ``bf16_ceil(dominance * degree)``, or 1 where the degree is 0.
    """
    n = 1 << scale
    i, j = kronecker_edges(scale, edgefactor, initiator, rng)
    off = i != j
    i, j = i[off], j[off]
    adj = sp.csr_matrix((np.ones(2 * i.size), (np.r_[i, j], np.r_[j, i])),
                        shape=(n, n))
    adj.sum_duplicates()
    adj.data[:] = 1.0
    deg = np.diff(adj.indptr).astype(np.float64)
    diag = np.where(deg > 0, bf16_ceil(np.maximum(dominance * deg, 1.0)), 1.0)
    a = (sp.diags(diag, format="csr") - adj).tocsr()
    a.sort_indices()
    return a


def build(cfg: dict) -> sp.csr_matrix:
    p = cfg["params"]
    return graph500_laplacian(p["scale"], p["edgefactor"], p["initiator"],
                              p["dominance"],
                              S.rng_for(p["matrix_seed"], S.STREAM_MATRIX))


def search_keys(cfg: dict, a: sp.csr_matrix) -> np.ndarray:
    """The configuration's search keys: distinct vertices of degree >= 1."""
    p = cfg["params"]
    linked = np.flatnonzero(np.diff(a.indptr) > 1)
    return S.rng_for(p["matrix_seed"], S.STREAM_PICK).choice(
        linked, p["search_keys"], replace=False)


def rhs(cfg: dict, a: sp.csr_matrix, seed: int, j: int) -> np.ndarray:
    """Right-hand side ``j`` of a run: ``e_v`` for its search key ``v``."""
    keys = search_keys(cfg, a)
    cycle, pos = divmod(int(j), keys.size)
    v = keys[S.rng_for(seed, S.STREAM_RHS, cycle).permutation(keys.size)[pos]]
    b = np.zeros(a.shape[0])
    b[v] = 1.0
    return b
