"""The generators: HPCG's stencil and the Graph500 Laplacian, small,
and how a configuration finds its generator by name."""
import json
import pathlib

import ml_dtypes
import numpy as np
import pytest
import scipy.sparse as sp
import scipy.sparse.linalg  # noqa: F401

from bench import systems as S

G27 = S.generator("hpcg_stencil27")
G500 = S.generator("graph500_laplacian")
CONFIGS = pathlib.Path(__file__).resolve().parents[1] / "configs"


def exact_in_bf16(v) -> bool:
    v = np.asarray(v, np.float64)
    return np.array_equal(v.astype(ml_dtypes.bfloat16).astype(np.float64), v)


@pytest.fixture(scope="module")
def hpcg():
    return G27.hpcg_stencil27(6, 5, 4)


@pytest.fixture(scope="module")
def g500():
    return G500.graph500_laplacian(8, 16, (0.57, 0.19, 0.19, 0.05), 1.2,
                                S.rng_for(500, S.STREAM_MATRIX, 0))


def test_hpcg_rows(hpcg):
    nx, ny, nz = 6, 5, 4
    nnz = np.diff(hpcg.indptr)
    x, y, z = np.meshgrid(np.arange(nx), np.arange(ny), np.arange(nz),
                          indexing="ij")
    i = (x + nx * (y + ny * z)).ravel()
    inner = ((x > 0) & (x < nx - 1) & (y > 0) & (y < ny - 1) & (z > 0)
             & (z < nz - 1)).ravel()
    assert (nnz[i[inner]] == 27).all()
    assert nnz[0] == 8                     # a corner keeps 7 neighbours
    assert (hpcg.diagonal() == 26).all()
    off = hpcg - sp.diags(hpcg.diagonal())
    assert set(np.unique(off.data)) == {-1.0}
    assert hpcg.has_sorted_indices


def test_hpcg_symmetric_dominant_exact(hpcg):
    assert abs(hpcg - hpcg.T).max() == 0
    offsum = np.asarray(abs(hpcg).sum(axis=1)).ravel() - 26
    assert (offsum <= 26).all() and (offsum < 26).any()
    assert exact_in_bf16(hpcg.data)


def test_hpcg_full_size_counts():
    """The configuration's stated rows and nonzeros, from the formula."""
    n = 104
    nnz_1d = 3 * n - 2                     # neighbours incl. self, 1-D
    assert n ** 3 == 1124864 and nnz_1d ** 3 == 29791000


def test_graph500_laplacian(g500):
    n = 1 << 8
    assert g500.shape == (n, n)
    assert abs(g500 - g500.T).max() == 0
    d = g500.diagonal()
    off = g500 - sp.diags(d)
    off.eliminate_zeros()
    assert set(np.unique(off.data)) <= {-1.0}
    deg = np.diff(off.tocsr().indptr)
    assert (d > deg).all()                 # strictly diagonally dominant
    assert (d[deg == 0] == 1).all()
    assert (d[deg > 0] >= 1.2 * deg[deg > 0]).all()
    assert exact_in_bf16(g500.data)
    assert g500.has_sorted_indices


def test_graph500_same_seed_same_matrix(g500):
    again = G500.graph500_laplacian(8, 16, (0.57, 0.19, 0.19, 0.05), 1.2,
                                 S.rng_for(500, S.STREAM_MATRIX, 0))
    other = G500.graph500_laplacian(8, 16, (0.57, 0.19, 0.19, 0.05), 1.2,
                                 S.rng_for(501, S.STREAM_MATRIX, 0))
    assert (g500 != again).nnz == 0
    assert g500.nnz != other.nnz or (g500 != other).nnz > 0


def test_kronecker_skew():
    """Graph500's initiator puts most edges on few vertices."""
    ij = G500.kronecker_edges(10, 16, (0.57, 0.19, 0.19, 0.05),
                           np.random.default_rng(0))
    assert ij.shape == (2, 16 << 10) and ij.min() >= 0 and ij.max() < 1024
    deg = np.bincount(ij.ravel(), minlength=1024)
    assert deg.max() > 20 * np.median(deg[deg > 0])


@pytest.mark.parametrize("v", [1.0, 1.2, 2.4, 300.1, 4299.6, 7.0])
def test_bf16_ceil(v):
    c = float(G500.bf16_ceil(v))
    assert c >= v and exact_in_bf16(c)
    m, e = np.frexp(c)                     # the bf16 value just below c
    below = c - np.ldexp(1.0, e - (8 if m > 0.5 else 9))
    assert exact_in_bf16(below) and below < v


def small_g500(**params):
    return {"generator": "graph500_laplacian",
            "params": {"scale": 8, "edgefactor": 16, "dominance": 1.2,
                       "initiator": [0.57, 0.19, 0.19, 0.05],
                       "matrix_seed": 3, "search_keys": 16, **params}}


def test_operator_from_matrix_seed_only():
    a, b = S.Systems(small_g500()).a, S.Systems(small_g500()).a
    c = S.Systems(small_g500(matrix_seed=4)).a
    assert (a != b).nnz == 0
    assert a.nnz != c.nnz or (a != c).nnz > 0


@pytest.mark.parametrize("path", sorted(CONFIGS.glob("*.json")),
                         ids=lambda p: p.stem)
def test_every_configuration_finds_its_generator(path):
    """Its generator defines ``build``, ``rhs`` and ``SMALL``, the CPU
    test size, whose overrides name keys the configuration has."""
    cfg = json.loads(path.read_text())
    gen = S.generator(cfg["generator"])
    assert callable(gen.build) and callable(gen.rhs)
    assert isinstance(getattr(gen, "SMALL", None), dict), \
        f"{S.GENERATORS / cfg['generator']}.py defines no SMALL"
    for key, val in gen.SMALL.items():
        assert key in cfg
        if isinstance(val, dict):
            assert set(val) <= set(cfg[key]), key


def test_unknown_generator_is_an_error():
    with pytest.raises(ValueError):
        S.generator("no_such_generator")


def test_search_keys_each_once_per_cycle():
    syst = S.Systems(small_g500())
    keys = G500.search_keys(syst.cfg, syst.a)
    assert len(set(keys)) == 16
    assert (np.diff(syst.a.indptr)[keys] >= 2).all()     # degree >= 1
    seed = 2 ** 33 + 5
    got = [int(np.flatnonzero(syst.rhs(seed, j))[0]) for j in range(32)]
    assert sorted(got[:16]) == sorted(keys) == sorted(got[16:])
    assert got[:16] != got[16:]
    assert all(syst.rhs(seed, j).sum() == 1.0 for j in range(3))
    again = [int(np.flatnonzero(syst.rhs(seed, j))[0]) for j in range(16)]
    other = [int(np.flatnonzero(syst.rhs(seed + 1, j))[0])
             for j in range(16)]
    assert again == got[:16] and other != got[:16]


def test_hpcg_rhs_from_seed():
    cfg = {"generator": "hpcg_stencil27",
           "params": {"nx": 4, "ny": 3, "nz": 2},
           "rhs": {"low": 0.5, "high": 1.5}}
    syst = S.Systems(cfg)
    big = 2 ** 33 + 12345
    b1, b2 = syst.rhs(big, 1), syst.rhs(big, 1)
    assert np.array_equal(b1, b2)
    assert not np.array_equal(b1, syst.rhs(big, 2))
    x = sp.linalg.spsolve(syst.a.tocsc(), b1)
    assert ((x >= 0.5) & (x < 1.5)).all()
