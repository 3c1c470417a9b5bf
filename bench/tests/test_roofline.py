"""The least bytes of an SpMV come from the system, not its layout."""
import types

import numpy as np
import pytest
import scipy.sparse as sp

from bench import roofline, systems as S


def csr_of(a):
    from repro.sparse import CSRMatrix
    return CSRMatrix(a.indptr.astype(np.int64), a.indices.astype(np.int32),
                     a.data, a.shape)


def from_ellpack(m, shape):
    b, t, l, r = np.nonzero(m.vals)
    rows = b * m.block_rows + r
    cols = m.tile_cols[b, t] * m.col_tile + m.local_cols[b, t, l, r]
    return sp.csr_matrix((m.vals[b, t, l, r], (rows, cols)), shape=shape)


def from_sell(st, shape):
    perm = np.argsort(st.iperm[0])             # sorted position -> row
    rows, cols, vals = [], [], []
    off = r0 = 0
    for n_rows, w in st.groups:
        c = st.cols[0, off:off + n_rows * w].reshape(w, n_rows).T
        v = st.vals[0, off:off + n_rows * w].reshape(w, n_rows).T
        rr = np.broadcast_to(perm[r0:r0 + n_rows, None], c.shape)
        keep = v != 0
        rows.append(rr[keep]), cols.append(c[keep]), vals.append(v[keep])
        off += n_rows * w
        r0 += n_rows
    return sp.csr_matrix((np.concatenate(vals).astype(np.float64),
                          (np.concatenate(rows), np.concatenate(cols))),
                         shape=shape)


@pytest.fixture(scope="module", params=["hpcg", "graph500"])
def matrix(request):
    if request.param == "hpcg":
        return S.generator("hpcg_stencil27").hpcg_stencil27(12, 10, 9)
    return S.generator("graph500_laplacian").graph500_laplacian(
        10, 16, (0.57, 0.19, 0.19, 0.05), 1.2,
        S.rng_for(500, S.STREAM_MATRIX, 0))


def test_same_work_for_ellpack_and_sell(matrix):
    from repro.sparse.ellpack import csr_to_ellpack
    from repro.sparse.stacking import stack_sell
    a = csr_of(matrix)
    ell = csr_to_ellpack(a, block_rows=128, col_tile=256)
    sell = stack_sell([a])
    back_e = from_ellpack(ell, matrix.shape)
    back_s = from_sell(sell, matrix.shape)
    for back in (back_e, back_s):
        assert (back != matrix).nnz == 0
        assert roofline.spmv_min_bytes(back) == \
            roofline.spmv_min_bytes(matrix)
    # the layouts themselves store different amounts of padding
    assert ell.vals.size != sell.vals.size


def test_hpcg_104_bytes():
    a = types.SimpleNamespace(shape=(1124864, 1124864), nnz=29791000)
    got = roofline.spmv_min_bytes(a, value_bytes=2, vector_bytes=4)
    assert got == 29791000 * (2 + 4) + 2 * 1124864 * 4


def test_index_width():
    assert roofline.index_bytes(1 << 14) == 2
    assert roofline.index_bytes(1 << 15) == 2
    assert roofline.index_bytes((1 << 15) + 1) == 4


def test_peaks_table():
    p = roofline.peaks_of("TPU v5 lite")
    assert p["hbm_bytes_per_s"] == 819e9 and p["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        roofline.peaks_of("cpu")
