"""Sparse substrate: CSR / banked-ELL / ELLPACK / partition / mtx IO."""
import os

import numpy as np
import pytest
from _hyp import given, settings, strategies as st

from repro.sparse import (CSRMatrix, bell_spmv_reference, csr_from_coo,
                          csr_spmv, csr_to_bell, csr_to_dense,
                          diag_dominant_spd, partition_rows, poisson_2d,
                          poisson_3d, random_spd, read_mtx, tridiagonal_spd,
                          write_mtx)
from repro.sparse.ellpack import csr_to_ellpack, ellpack_spmv_reference

FAST = dict(deadline=None, max_examples=15)


class TestCSR:
    def test_coo_roundtrip_with_duplicates(self):
        rows = np.array([0, 0, 1, 0])
        cols = np.array([1, 0, 1, 1])
        vals = np.array([2.0, 1.0, 5.0, 3.0])
        a = csr_from_coo(rows, cols, vals, (2, 2))
        d = csr_to_dense(a)
        np.testing.assert_array_equal(d, [[1.0, 5.0], [0.0, 5.0]])

    def test_diagonal(self):
        a = poisson_2d(8)
        np.testing.assert_array_equal(a.diagonal(), np.full(64, 4.0))

    @given(n=st.integers(4, 64), seed=st.integers(0, 100))
    @settings(**FAST)
    def test_spmv_matches_dense(self, n, seed):
        a = diag_dominant_spd(n, nnz_per_row=6, dominance=1.5, seed=seed)
        x = np.random.default_rng(seed).standard_normal(n)
        np.testing.assert_allclose(csr_spmv(a, x), csr_to_dense(a) @ x,
                                   rtol=1e-12)


class TestGenerators:
    @pytest.mark.parametrize("make,n", [
        (lambda: poisson_2d(12), 144),
        (lambda: poisson_3d(5), 125),
        (lambda: tridiagonal_spd(64), 64),
        (lambda: diag_dominant_spd(80, seed=1), 80),
        (lambda: random_spd(24, seed=1), 24),
    ])
    def test_spd(self, make, n):
        a = make()
        assert a.shape == (n, n)
        d = csr_to_dense(a)
        np.testing.assert_allclose(d, d.T, atol=1e-12)
        w = np.linalg.eigvalsh(d)
        assert w.min() > 0, f"not PD: λmin={w.min()}"

    def test_random_spd_condition(self):
        a = random_spd(32, cond=1e3, seed=0)
        w = np.linalg.eigvalsh(csr_to_dense(a))
        assert w.max() / w.min() == pytest.approx(1e3, rel=0.05)


class TestBell:
    @given(n=st.integers(8, 120), seed=st.integers(0, 50))
    @settings(**FAST)
    def test_bell_spmv_matches(self, n, seed):
        a = diag_dominant_spd(n, nnz_per_row=8, dominance=1.4, seed=seed)
        m = csr_to_bell(a, block_rows=8, col_tile=16)
        x = np.random.default_rng(seed).standard_normal(n)
        np.testing.assert_allclose(bell_spmv_reference(m, x),
                                   csr_to_dense(a) @ x, rtol=1e-10,
                                   atol=1e-10)

    def test_nnz_preserved(self):
        a = poisson_2d(10)
        m = csr_to_bell(a, block_rows=16, col_tile=32)
        assert m.nnz == a.nnz
        assert 0 < m.padding_efficiency <= 1.0

    def test_stream_bytes_ordering(self):
        """Lower precision ⇒ smaller matrix stream (Challenge 3)."""
        a = poisson_2d(10)
        m = csr_to_bell(a, block_rows=16, col_tile=32)
        assert m.stream_bytes(2) < m.stream_bytes(4) < m.stream_bytes(8)


class TestEllpack:
    @given(n=st.integers(8, 150), nnz=st.integers(2, 12),
           seed=st.integers(0, 50))
    @settings(**FAST)
    def test_ellpack_matches_dense(self, n, nnz, seed):
        a = diag_dominant_spd(n, nnz_per_row=nnz, dominance=1.4, seed=seed)
        m = csr_to_ellpack(a, block_rows=8, col_tile=16)
        x = np.random.default_rng(seed).standard_normal(n)
        np.testing.assert_allclose(ellpack_spmv_reference(m, x),
                                   csr_to_dense(a) @ x, rtol=1e-10,
                                   atol=1e-10)

    def test_local_indices_fit_int16(self):
        """The Serpens-style packing claim: local col ids < col_tile."""
        a = poisson_2d(32)
        m = csr_to_ellpack(a, block_rows=128, col_tile=512)
        assert m.local_cols.max() < 512 <= 32768


class TestPartition:
    @pytest.mark.parametrize("n_shards", [1, 2, 4, 7])
    def test_partition_preserves_matrix(self, n_shards):
        a = poisson_2d(12)                       # n=144
        part = partition_rows(a, n_shards, block_rows=8, col_tile=16)
        x = np.random.default_rng(0).standard_normal(144)
        want = csr_to_dense(a) @ x
        got = np.zeros(part.padded_rows)
        for k in range(n_shards):
            sh = part.shard(k)
            xp = x
            got[k * part.rows_per_shard:(k + 1) * part.rows_per_shard] = \
                bell_spmv_reference(sh, xp)
        np.testing.assert_allclose(got[:144], want, rtol=1e-10, atol=1e-10)

    def test_halo_width_stencil(self):
        """Stencil matrices report a narrow halo (enables ppermute)."""
        a = poisson_2d(16)                       # bandwidth 16
        part = partition_rows(a, 4, block_rows=8, col_tile=16)
        assert 0 < part.halo_width <= 16


class TestMtxIO:
    def test_roundtrip(self, tmp_path):
        a = diag_dominant_spd(20, nnz_per_row=4, seed=3)
        p = os.path.join(tmp_path, "m.mtx")
        write_mtx(p, a)
        b = read_mtx(p)
        np.testing.assert_allclose(csr_to_dense(a), csr_to_dense(b),
                                   rtol=1e-12)

    def test_symmetric_storage(self, tmp_path):
        """SuiteSparse symmetric .mtx stores the lower triangle only."""
        a = poisson_2d(4)
        p = os.path.join(tmp_path, "sym.mtx")
        write_mtx(p, a, symmetric=True)
        b = read_mtx(p)
        np.testing.assert_allclose(csr_to_dense(a), csr_to_dense(b),
                                   rtol=1e-12)


class TestStacking:
    """Batched padding/stacking helpers (repro.sparse.stacking)."""

    def _bells(self):
        from repro.sparse import csr_to_bell
        return [csr_to_bell(a, block_rows=8, col_tile=128) for a in
                (poisson_2d(13), tridiagonal_spd(250),
                 diag_dominant_spd(150, nnz_per_row=6, seed=3))]

    def test_pad_bell_preserves_product(self):
        from repro.sparse.stacking import pad_bell
        for m in self._bells():
            big = pad_bell(m, n_row_blocks=m.n_row_blocks + 3,
                           n_slabs=m.n_slabs + 2, slab_len=m.slab_len + 8)
            x = np.random.default_rng(0).standard_normal(m.shape[1])
            np.testing.assert_allclose(bell_spmv_reference(big, x),
                                       bell_spmv_reference(m, x))

    def test_stack_bell_buckets_and_preserves(self):
        from repro.sparse.stacking import bucket_up, stack_bell
        bells = self._bells()
        s = stack_bell(bells)
        assert s.batch == 3
        # every structural dim landed on a power-of-two bucket edge
        for d in s.vals.shape[1:] + (s.n_col_tiles,):
            assert d == bucket_up(d)
        # padding is pure zeros: per-lane nnz mass is preserved
        for g, m in enumerate(bells):
            assert np.count_nonzero(s.vals[g]) == np.count_nonzero(m.vals)

    def test_flatten_bell_stream_matches_csr(self):
        """The packed (col, val, row) stream IS the matrix: scatter-adding
        it reproduces the CSR SpMV."""
        from repro.sparse.stacking import flatten_bell
        for a in (poisson_2d(13), tridiagonal_spd(250)):
            from repro.sparse import csr_to_bell
            m = csr_to_bell(a, block_rows=8, col_tile=128)
            gc, v, rw = flatten_bell(m)
            x = np.random.default_rng(1).standard_normal(m.padded_cols)
            y = np.zeros(m.padded_rows)
            np.add.at(y, rw, v * x[gc])
            np.testing.assert_allclose(y[: a.shape[0]],
                                       csr_spmv(a, x[: a.shape[1]]))

    def test_stack_flat_zero_extension(self):
        """Streams zero-extend to any bucket without changing the product."""
        from repro.sparse.stacking import stack_flat
        bells = self._bells()
        s = stack_flat(bells)
        assert s.gcols.shape == s.vals.shape == s.rows.shape
        for g, m in enumerate(bells):
            x = np.random.default_rng(g).standard_normal(s.padded_cols)
            y = np.zeros(s.padded_rows)
            np.add.at(y, s.rows[g], s.vals[g] * x[s.gcols[g]])
            ref = bell_spmv_reference(m, x[: m.shape[1]])
            np.testing.assert_allclose(y[: m.shape[0]], ref)


class TestFineBucket:
    """Eighth-octave edges of the ELLPACK operand against the
    power-of-two edges every other stacked layout keeps."""

    def test_fine_bucket_up_edges(self):
        from repro.sparse.stacking import bucket_up, fine_bucket_up
        assert [fine_bucket_up(x) for x in range(1, 17)] == \
            list(range(1, 17))
        assert [fine_bucket_up(x) for x in (17, 19, 33, 2197, 4394)] == \
            [18, 20, 36, 2304, 4608]
        prev = 0
        for x in range(1, 10**6 + 1):
            e = fine_bucket_up(x)
            assert x <= e < x * 1.125
            assert e >= prev
            prev = e
        for x in range(1, 5000):
            e = fine_bucket_up(x)
            assert fine_bucket_up(e) == e
            assert e <= bucket_up(x)
        for k in range(31):
            assert fine_bucket_up(1 << k) == 1 << k

    def _ellpacks(self):
        return [csr_to_ellpack(a, block_rows=8, col_tile=16) for a in
                (diag_dominant_spd(300, nnz_per_row=6, seed=3),
                 poisson_2d(13), tridiagonal_spd(250),
                 random_spd(6, cond=10.0, seed=1))]

    def test_stack_ellpack_fine_edges(self):
        from repro.sparse.ellpack import EllpackMatrix
        from repro.sparse.stacking import (bucket_up, fine_bucket_up,
                                           stack_ellpack)
        ells = self._ellpacks()
        B, T, E, n_tiles = (max(getattr(m, k) for m in ells) for k in
                            ("n_row_blocks", "n_slabs", "ell",
                             "n_col_tiles"))
        # the bag reaches past 16 off the power-of-two edges; its slot
        # count (6, the dense lane) is off them too
        assert min(B, T, n_tiles) > 16
        for d in (B, T, E, n_tiles):
            assert fine_bucket_up(d) < bucket_up(d)
        s = stack_ellpack(ells)
        assert s.vals.shape[1:] == (fine_bucket_up(B), fine_bucket_up(T),
                                    bucket_up(E), 8)
        assert s.local_cols.shape == s.vals.shape
        assert s.tile_cols.shape == s.vals.shape[:3]
        assert s.n_col_tiles == fine_bucket_up(n_tiles)
        exact = stack_ellpack(ells, bucket=False)
        assert exact.vals.shape[1:] == (B, T, E, 8)
        assert exact.n_col_tiles == n_tiles
        # padding is exact zeros: every lane's product is unchanged
        for g, m in enumerate(ells):
            lane = EllpackMatrix(s.tile_cols[g], s.vals[g], s.local_cols[g],
                                 m.shape, m.block_rows, m.col_tile, m.nnz)
            x = np.random.default_rng(g).standard_normal(m.shape[1])
            np.testing.assert_array_equal(ellpack_spmv_reference(lane, x),
                                          ellpack_spmv_reference(m, x))

    def test_sell_rowell_stay_power_of_two(self):
        from repro.sparse.stacking import (bucket_up, fine_bucket_up,
                                           stack_rowell, stack_sell)
        bag = [diag_dominant_spd(300, nnz_per_row=6, seed=3),
               poisson_2d(13), tridiagonal_spd(250)]
        assert fine_bucket_up(300) < bucket_up(300)
        r = stack_rowell(bag)
        assert r.padded_rows == bucket_up(300) == 512
        assert r.width == bucket_up(r.width)
        s = stack_sell(bag)
        assert s.padded_rows == 512
        for rows, w in s.groups:
            assert w == 0 or w == bucket_up(w)
