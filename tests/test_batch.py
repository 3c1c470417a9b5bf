"""Batched multi-system JPCG: lane-vs-single parity, on-the-fly per-lane
termination, bucket compile-cache reuse, SolverEngine admission."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.batch import (batch_cache_clear, batch_cache_info,
                              jpcg_solve_batched)
from repro.core.cg import jpcg_solve
from repro.core.precision import get_scheme
from repro.sparse import (CSRMatrix, csr_to_dense, diag_dominant_spd,
                          poisson_2d, random_spd, tridiagonal_spd)
from repro.sparse.stacking import bucket_up
from repro.serve.solver_engine import SolverEngine, SolverEngineConfig
from oracles import (assert_lane_equal, assert_results_bit_identical,
                     assert_vm_states_equal)

BK = dict(block_rows=8, col_tile=128)


def _mixed_bag():
    """≥8 heterogeneous SPD systems: different n, conditioning, sparsity."""
    return [
        poisson_2d(16),                                                 # 256
        tridiagonal_spd(300),                                           # 300
        diag_dominant_spd(200, nnz_per_row=8, dominance=1.3, seed=2),
        random_spd(64, cond=100.0, seed=1),
        poisson_2d(20),                                                 # 400
        tridiagonal_spd(128, off=-0.4),          # easy: converges early
        diag_dominant_spd(400, nnz_per_row=12, dominance=1.05, seed=5),
        random_spd(100, cond=1e3, seed=9),
    ]


class TestBatchedParity:
    def test_lanes_match_single_solver(self):
        """Each lane of one compiled batched solve reproduces the
        single-system solver: iterations within ±2, x to tolerance."""
        probs = _mixed_bag()
        assert len(probs) >= 8
        res = jpcg_solve_batched(probs, tol=1e-12, maxiter=4000, **BK)
        for a, r in zip(probs, res):
            ref = jpcg_solve(a, tol=1e-12, maxiter=4000, **BK)
            assert r.converged and ref.converged
            # the batched matvec reduces rows through the deterministic
            # halving tree (layout bit-interchangeability), the single
            # solver through banked tiles — different rounding, so the
            # cond≈1e3 lane can stop a step or two apart near ‖r‖²≈tol
            assert abs(r.iterations - ref.iterations) <= 2
            # both stopped at ‖r‖² ≤ 1e-12, i.e. ‖r‖ ≈ 1e-6: the two
            # near-solutions may differ by one final update of that size
            np.testing.assert_allclose(np.asarray(r.x), np.asarray(ref.x),
                                       rtol=1e-4, atol=1e-6)

    @pytest.mark.slow
    def test_solution_solves_system(self):
        probs = _mixed_bag()
        res = jpcg_solve_batched(probs, tol=1e-12, maxiter=4000, **BK)
        for a, r in zip(probs, res):
            d = csr_to_dense(a)
            x = np.asarray(r.x)
            b = np.ones(a.shape[0])
            assert np.linalg.norm(d @ x - b) <= 1e-4 * np.linalg.norm(b)

    @pytest.mark.parametrize("scheme", ["fp64", "mixed_v3"])
    def test_schemes(self, scheme):
        probs = [poisson_2d(12), tridiagonal_spd(200)]
        res = jpcg_solve_batched(probs, tol=1e-12, maxiter=2000,
                                 scheme=scheme, **BK)
        for a, r in zip(probs, res):
            ref = jpcg_solve(a, tol=1e-12, maxiter=2000, scheme=scheme, **BK)
            assert abs(r.iterations - ref.iterations) <= 1

    def test_custom_rhs_x0_and_per_problem_tol(self):
        probs = [poisson_2d(12), poisson_2d(14)]
        rng = np.random.default_rng(0)
        bs = [rng.standard_normal(a.shape[0]) for a in probs]
        d0 = csr_to_dense(probs[0])
        xstar0 = np.linalg.solve(d0, bs[0])
        x0s = [xstar0, np.zeros(probs[1].shape[0])]
        res = jpcg_solve_batched(probs, bs, x0s=x0s,
                                 tol=[1e-10, 1e-12], maxiter=2000, **BK)
        # lane 0 started at its solution: terminates immediately
        assert res[0].iterations <= 1
        assert res[1].converged and res[1].rr <= 1e-12


class TestOnTheFlyTermination:
    def test_early_lane_freezes(self):
        """An easy lane converges early and its x stops updating while the
        hard lane keeps iterating (per-problem termination in one loop)."""
        easy = tridiagonal_spd(256, off=-0.1)
        hard = tridiagonal_spd(256)
        res = jpcg_solve_batched([easy, hard], tol=1e-12, maxiter=1000,
                                 with_trace=True, **BK)
        assert res[0].iterations < res[1].iterations
        # frozen lane's result equals its own single solve (no extra drift
        # from the iterations the batch kept running)
        ref = jpcg_solve(easy, tol=1e-12, maxiter=1000, **BK)
        assert abs(res[0].iterations - ref.iterations) <= 1
        np.testing.assert_allclose(np.asarray(res[0].x), np.asarray(ref.x),
                                   rtol=1e-9)
        # trace stops exactly at the lane's own iteration count
        assert res[0].residual_trace.shape[0] == res[0].iterations
        assert res[0].residual_trace[-1] <= 1e-12

    def test_maxiter_respected_per_batch(self):
        a = diag_dominant_spd(500, nnz_per_row=12, dominance=1.01, seed=1)
        res = jpcg_solve_batched([a, poisson_2d(8)], tol=1e-30, maxiter=7,
                                 **BK)
        assert res[0].iterations == 7 and not res[0].converged
        assert res[1].iterations == 7 and not res[1].converged


class TestBucketCache:
    def test_same_bucket_reuses_runner(self):
        """Two different heterogeneous batches landing in the same bucket
        share one compiled runner (the handful-of-executables goal)."""
        batch_cache_clear()
        jpcg_solve_batched([poisson_2d(12), tridiagonal_spd(200)],
                           tol=1e-12, maxiter=500, **BK)
        info1 = batch_cache_info()
        jpcg_solve_batched([poisson_2d(11), tridiagonal_spd(180)],
                           tol=1e-12, maxiter=500, **BK)
        info2 = batch_cache_info()
        assert info1["entries"] == 1 and info1["misses"] == 1
        assert info2["entries"] == 1 and info2["hits"] == info1["hits"] + 1

    def test_bucket_up_edges(self):
        assert [bucket_up(x) for x in (1, 2, 3, 5, 8, 9)] == \
            [1, 2, 4, 8, 8, 16]

    def test_ellpack_fine_bucket_matches_exact(self):
        """A Pallas ELLPACK bag whose row blocks (104) and slabs (7) fall
        off the power-of-two edges: ``bucket=True`` keeps both (104 =
        13·8 is an eighth-octave edge) and pads only the slots 6 -> 8,
        with exact zeros — so it solves bit-identically to
        ``bucket=False``."""
        from repro.sparse.ellpack import csr_to_ellpack
        from repro.sparse.stacking import stack_ellpack
        probs = [diag_dominant_spd(832, nnz_per_row=5, dominance=1.2,
                                   seed=3), poisson_2d(12)]
        ells = [csr_to_ellpack(a, **BK) for a in probs]
        assert stack_ellpack(ells, bucket=False).vals.shape[1:] == \
            (104, 7, 6, 8)
        assert stack_ellpack(ells).vals.shape[1:] == (104, 7, 8, 8)
        kw = dict(tol=1e-10, maxiter=300, backend="pallas",
                  layout="ellpack", **BK)
        fine = jpcg_solve_batched(probs, bucket=True, **kw)
        exact = jpcg_solve_batched(probs, bucket=False, **kw)
        assert all(r.status == "CONVERGED" for r in exact)
        assert_results_bit_identical(fine, exact, status=True, rr=True)


def _near_bf16_ties(a):
    """``a`` with every value moved to just above a bf16 rounding tie,
    by less than a bf16 ulp: float64 -> float32 -> bf16 rounds it to
    even, where one rounding from float64 would round it up."""
    v = np.abs(a.data)
    ulp = 2.0 ** (np.floor(np.log2(v)) - 7)
    tie = (np.floor(v / ulp) + 0.5) * ulp
    data = np.sign(a.data) * tie * (1 + 2.0 ** -40)
    return CSRMatrix(a.indptr, a.indices, data, a.shape)


def _operator(seed, n=300):
    return _near_bf16_ties(diag_dominant_spd(n, nnz_per_row=6,
                                             dominance=1.2, seed=seed))


def _copy(a):
    return CSRMatrix(a.indptr.copy(), a.indices.copy(), a.data.copy(),
                     a.shape)


def _bag(name):
    """Bags of lanes that share operators, by object or by value, and
    one whose two operators differ in one value's last bit."""
    a, b = _operator(3), _operator(4, n=260)
    a1 = _copy(a)
    a1.data[5] = np.nextafter(a1.data[5], np.inf)
    return {"AA": [a, a], "ABA": [a, b, a], "A-copyA": [a, _copy(a)],
            "A-A1": [a, a1]}[name]


def _per_lane_operand(lanes, scheme, bucket):
    """The ELLPACK operand as one pack per lane gives it: each lane
    packed, padded and stacked in float64, put, then cast on the
    device."""
    from repro.sparse.ellpack import csr_to_ellpack
    from repro.sparse.stacking import fine_bucket_up, pad_ellpack
    ells = [csr_to_ellpack(a, **BK) for a in lanes]
    fine = fine_bucket_up if bucket else int
    B = fine(max(m.n_row_blocks for m in ells))
    T = fine(max(m.n_slabs for m in ells))
    E = (bucket_up if bucket else int)(max(m.ell for m in ells))
    padded = [pad_ellpack(m, n_row_blocks=B, n_slabs=T, ell=E)
              for m in ells]
    tc, v, lc = (jnp.asarray(np.stack([getattr(m, k) for m in padded]))
                 for k in ("tile_cols", "vals", "local_cols"))
    return tc, v.astype(get_scheme(scheme).matrix_dtype), lc


def _prepare_ellpack(lanes, scheme, bucket):
    from repro.core.batch import _prepare
    return _prepare(lanes, None, None, 1e-10, scheme=get_scheme(scheme),
                    backend="pallas", layout="ellpack", bucket=bucket,
                    mesh=None, rid=0, **BK)


class TestSharedOperator:
    """A bag's lanes that hold one operator share one pack: the device
    operand, and so every result, is bit-identical to packing each
    lane."""

    @pytest.mark.parametrize("x64", [True, False])
    @pytest.mark.parametrize("bucket", [True, False])
    @pytest.mark.parametrize("name,operands", [
        ("AA", 1), ("ABA", 2), ("A-copyA", 1), ("A-A1", 2)])
    def test_operand_bytes_match_per_lane_pack(self, name, operands,
                                               bucket, x64):
        lanes = _bag(name)
        with jax.enable_x64(x64):
            bag = _prepare_ellpack(lanes, "tpu_v3", bucket)
            ref = _per_lane_operand(lanes, "tpu_v3", bucket)
            assert bag.operands == operands
            for got, want in zip(bag.mat, ref):
                got, want = np.asarray(got), np.asarray(want)
                assert got.dtype == want.dtype
                assert got.shape == want.shape
                np.testing.assert_array_equal(got.view(np.uint8),
                                              want.view(np.uint8))

    @pytest.mark.parametrize("scheme", ["tpu_v3", "tpu_v3_df"])
    @pytest.mark.parametrize("name", ["AA", "A-copyA"])
    def test_solves_match_per_lane_pack(self, name, scheme, monkeypatch):
        import repro.core.batch as batch
        lanes = _bag(name)
        kw = dict(tol=1e-10, maxiter=400, scheme=scheme, backend="pallas",
                  layout="ellpack", **BK)
        got = jpcg_solve_batched(lanes, **kw)
        prepare = batch._prepare

        def per_lane(*args, **kwargs):
            bag = prepare(*args, **kwargs)
            return bag._replace(mat=_per_lane_operand(
                lanes, scheme, kwargs["bucket"]))

        monkeypatch.setattr(batch, "_prepare", per_lane)
        ref = jpcg_solve_batched(lanes, **kw)
        assert all(r.status == "CONVERGED" for r in ref)
        assert_results_bit_identical(got, ref, rr=True, status=True)

    @pytest.mark.parametrize("name,operands", [("AA", 1), ("ABA", 2)])
    def test_operands_packed_counter(self, name, operands):
        from repro.core.metrics import solver_metrics
        m = solver_metrics()
        before = m.get("operands_packed")
        jpcg_solve_batched(_bag(name), tol=1e-10, maxiter=5,
                           scheme="tpu_v3", backend="pallas",
                           layout="ellpack", **BK)
        assert m.get("operands_packed") - before == operands

    def test_no_operand_kept_across_calls(self):
        """A call packs what its lanes hold now: a second call after the
        operator's values changed in place solves the new operator."""
        a = _operator(5)
        kw = dict(tol=1e-10, maxiter=400, scheme="tpu_v3",
                  backend="pallas", layout="ellpack", **BK)
        first = jpcg_solve_batched([a, a], **kw)
        a.data[:] *= 2.0
        second = jpcg_solve_batched([a, a], **kw)
        fresh = _copy(a)
        ref = jpcg_solve_batched([fresh, fresh], **kw)
        assert_results_bit_identical(second, ref, rr=True, status=True)
        assert not np.array_equal(np.asarray(first[0].x),
                                  np.asarray(second[0].x))


class TestSolverEngine:
    def test_admission_and_harvest(self):
        eng = SolverEngine(SolverEngineConfig(batch_slots=4, chunk_iters=32,
                                              **BK))
        probs = {0: poisson_2d(16), 1: tridiagonal_spd(300),
                 2: diag_dominant_spd(200, nnz_per_row=8, dominance=1.3,
                                      seed=2)}
        ids = {k: eng.submit(a) for k, a in probs.items()}
        eng.step()
        # a slot freed mid-flight admits a new system without disturbing
        # the in-flight lanes — DecodeEngine-style continuous batching
        ids[3] = eng.submit(poisson_2d(20))
        probs[3] = poisson_2d(20)
        eng.run_to_completion()
        for k, a in probs.items():
            ref = jpcg_solve(a, tol=1e-12, maxiter=20_000, **BK)
            got = eng.results[ids[k]]
            assert got.converged
            assert abs(got.iterations - ref.iterations) <= 1
            np.testing.assert_allclose(np.asarray(got.x), np.asarray(ref.x),
                                       rtol=1e-6, atol=1e-8)

    def test_bucket_growth(self):
        eng = SolverEngine(SolverEngineConfig(batch_slots=2, chunk_iters=64,
                                              **BK))
        r1 = eng.submit(poisson_2d(12))
        eng.run_to_completion()
        r2 = eng.submit(poisson_2d(40))     # larger problem: bucket grows
        eng.run_to_completion()
        ref = jpcg_solve(poisson_2d(40), tol=1e-12, maxiter=20_000, **BK)
        assert abs(eng.results[r2].iterations - ref.iterations) <= 1
        assert eng.results[r1].converged and eng.results[r2].converged

    def test_slot_exhaustion_raises(self):
        eng = SolverEngine(SolverEngineConfig(batch_slots=1, **BK))
        eng.submit(poisson_2d(8))
        with pytest.raises(RuntimeError):
            eng.submit(poisson_2d(8))

    def test_per_request_maxiter(self):
        eng = SolverEngine(SolverEngineConfig(batch_slots=2, chunk_iters=8,
                                              **BK))
        hard = diag_dominant_spd(500, nnz_per_row=12, dominance=1.01, seed=1)
        rid = eng.submit(hard, tol=1e-30, maxiter=5)
        eng.run_to_completion()
        assert eng.results[rid].iterations == 5
        assert not eng.results[rid].converged

    def test_per_request_policy_shares_executable(self):
        """submit(policy=) routes to a separate pool, but with
        ``specialize=False`` pools differing only in policy share one
        jitted VM stepper — the program is an operand, not part of the
        cache key."""
        from repro.core.vm import vm_executable_stats
        eng = SolverEngine(SolverEngineConfig(batch_slots=2, chunk_iters=32,
                                              specialize=False, **BK))
        a = poisson_2d(16)
        r1 = eng.submit(a)                          # cfg default: paper
        eng.step()
        before = vm_executable_stats()
        r2 = eng.submit(a, policy="min_traffic")
        eng.run_to_completion()
        after = vm_executable_stats()
        assert after["traces"] == before["traces"]  # no new trace
        g1, g2 = eng.results[r1], eng.results[r2]
        assert g1.method == "vm_engine[paper]"
        assert g2.method == "vm_engine[min_traffic]"
        # same arithmetic, different traffic schedule: identical results
        assert g1.iterations == g2.iterations
        np.testing.assert_array_equal(np.asarray(g1.x), np.asarray(g2.x))

    def test_per_request_policy_costs_one_specialized_stepper(self):
        """Under the default specialized path a new policy costs exactly
        one specialized stepper (its program bytes differ) and leaves the
        generic-executable count untouched; results are still identical
        across policies."""
        from repro.core.vm import vm_executable_stats
        eng = SolverEngine(SolverEngineConfig(batch_slots=2, chunk_iters=32,
                                              **BK))
        a = poisson_2d(16)
        r1 = eng.submit(a)
        eng.step()
        before = vm_executable_stats()
        r2 = eng.submit(a, policy="min_traffic")
        eng.run_to_completion()
        after = vm_executable_stats()
        assert after["specialized"] == before["specialized"] + 1
        assert after["generic"] == before["generic"]
        g1, g2 = eng.results[r1], eng.results[r2]
        assert g1.iterations == g2.iterations
        np.testing.assert_array_equal(np.asarray(g1.x), np.asarray(g2.x))

    @pytest.mark.parametrize("specialize", [True, False])
    def test_bucket_growth_preserves_inflight_queues(self, specialize):
        """Regression (ISSUE 6): growing the bucket mid-flight must copy
        the queue file like ``mem`` — it used to be silently reset to
        zeros, corrupting any program that keeps streams live across
        iterations.  Also checks the in-flight lane still converges to
        the single-solver answer after growth.

        The two paths exercise different contracts (ISSUE 7): the
        generic stepper executes queue ops against the full state, so
        live streams are nonzero and must survive growth; the
        specialized stepper's dead-state analysis proves the canonical
        programs' queues phase-local — they *pass through* untouched
        (stay zero), which growth must likewise preserve."""
        eng = SolverEngine(SolverEngineConfig(
            batch_slots=2, chunk_iters=8, specialize=specialize, **BK))
        hard = tridiagonal_spd(300)
        r1 = eng.submit(hard)
        eng.step()                           # 8 iterations: queues live
        pool = eng._pool(None, None)
        assert bool(pool.state.active[0])    # still in flight
        q_before = np.asarray(pool.state.queues)
        if specialize:
            # pass-through contract: no live-in queues → bit-stable zeros
            assert np.all(q_before == 0.0)
        else:
            assert np.any(q_before != 0.0)
        m_before = np.asarray(pool.state.mem)

        r2 = eng.submit(poisson_2d(40))      # larger problem: bucket grows
        old_n = q_before.shape[-1]
        q_after = np.asarray(eng._pool(None, None).state.queues)
        assert q_after.shape[-1] > old_n
        # the in-flight lane's streams survived the grow (slot 0)
        assert np.array_equal(q_after[:, 0, :old_n], q_before[:, 0])
        assert np.all(q_after[:, 0, old_n:] == 0.0)
        assert np.array_equal(
            np.asarray(eng._pool(None, None).state.mem)[:, 0, :old_n],
            m_before[:, 0])

        eng.run_to_completion()
        for rid, a in ((r1, hard), (r2, poisson_2d(40))):
            ref = jpcg_solve(a, tol=1e-12, maxiter=20_000, **BK)
            got = eng.results[rid]
            assert got.converged
            assert abs(got.iterations - ref.iterations) <= 1
            np.testing.assert_allclose(np.asarray(got.x), np.asarray(ref.x),
                                       rtol=1e-6, atol=1e-8)

    @pytest.mark.parametrize("specialize", [True, False])
    def test_frozen_slot_state_is_bit_stable(self, specialize):
        """Regression (ISSUE 6): once a slot converges, its entire VM
        state — mem, queues, sregs, it — must be bit-stable while other
        slots keep iterating (``chunk_iters=1`` pins the check to the
        tick right after convergence, where the unmasked queue write
        drifted)."""
        eng = SolverEngine(SolverEngineConfig(
            batch_slots=2, chunk_iters=1, specialize=specialize, **BK))
        eng.submit(tridiagonal_spd(128, off=-0.1))   # easy: freezes first
        eng.submit(tridiagonal_spd(256))             # hard: keeps going
        pool = eng._pool(None, None)
        while bool(pool.state.active[0]) and bool(pool.state.active[1]):
            eng.step()
        frozen = 0 if not bool(pool.state.active[0]) else 1
        assert bool(pool.state.active[1 - frozen])
        snap = {f: np.asarray(getattr(pool.state, f))
                for f in ("mem", "queues", "sregs", "it")}
        eng.step()
        assert_vm_states_equal(pool.state, snap, lane=frozen)

    def test_free_slots_sums_across_pools(self):
        """free_slots() counts capacity across every instantiated pool
        (it used to see only the default pool); ``pool=`` restores the
        single-pool view and an uninstantiated pool reports its full
        capacity."""
        eng = SolverEngine(SolverEngineConfig(batch_slots=2, **BK))
        assert eng.free_slots() == 2                 # nothing materialized
        eng.submit(poisson_2d(8))                    # default pool
        eng.submit(poisson_2d(8), scheme="fp64")     # second pool
        assert eng.free_slots() == 2                 # one free in each
        assert eng.free_slots(pool=(None, None)) == 1
        assert eng.free_slots(pool=("fp64", None)) == 1
        assert eng.free_slots(pool=(None, "min_traffic")) == 2
        eng.run_to_completion()
        assert eng.free_slots() == 4                 # both pools drained

    def test_per_request_scheme(self):
        """submit(scheme=) solves that request at its own precision; the
        result records the scheme and matches the single-system solver."""
        eng = SolverEngine(SolverEngineConfig(batch_slots=2, chunk_iters=64,
                                              scheme="mixed_v3", **BK))
        a = tridiagonal_spd(200)
        r64 = eng.submit(a, scheme="fp64")
        rv3 = eng.submit(a)
        eng.run_to_completion()
        assert eng.results[r64].scheme == "fp64"
        assert eng.results[rv3].scheme == "mixed_v3"
        for rid, scheme in ((r64, "fp64"), (rv3, "mixed_v3")):
            ref = jpcg_solve(a, tol=1e-12, maxiter=20_000, scheme=scheme,
                             **BK)
            assert abs(eng.results[rid].iterations - ref.iterations) <= 1
            np.testing.assert_allclose(np.asarray(eng.results[rid].x),
                                       np.asarray(ref.x), rtol=1e-6,
                                       atol=1e-8)


class TestIterationChunking:
    """ISSUE 7: ``steps_per_sync`` runs k iterations per termination
    sync; every observable must stay bit-identical to k=1."""

    CHUNKS = (4, 8)

    def _solve(self, probs, k, *, engine, maxiter=2000, tol=1e-12, **kw):
        return jpcg_solve_batched(probs, tol=tol, maxiter=maxiter,
                                  with_trace=True, engine=engine,
                                  steps_per_sync=k, **kw, **BK)

    @pytest.mark.parametrize("engine,kw", [
        ("phases", {}),
        ("vm", {"specialize": True}),
        ("vm", {"specialize": False}),
    ])
    def test_chunk_sizes_bit_identical(self, engine, kw):
        """Per-lane solutions, iteration counts, final ‖r‖² and full
        residual traces agree bitwise across k ∈ {1, 4, 8} — including a
        lane that converges mid-chunk (the easy tridiagonal)."""
        probs = [poisson_2d(12), tridiagonal_spd(300),
                 tridiagonal_spd(128, off=-0.4)]
        base = self._solve(probs, 1, engine=engine, **kw)
        for k in self.CHUNKS:
            res = self._solve(probs, k, engine=engine, **kw)
            assert_results_bit_identical(res, base, rr=True, trace=True)

    @pytest.mark.parametrize("engine,kw", [
        ("phases", {}),
        ("vm", {"specialize": True}),
    ])
    def test_maxiter_not_multiple_of_chunk(self, engine, kw):
        """A lane that hits ``maxiter`` mid-chunk must stop at exactly
        ``maxiter`` iterations (never overshoot to the chunk edge) and
        report the same truncated trace for every k."""
        probs = [tridiagonal_spd(300)]
        base = self._solve(probs, 1, engine=engine, maxiter=37,
                           tol=1e-30, **kw)
        assert base[0].iterations == 37 and not base[0].converged
        for k in self.CHUNKS:
            res = self._solve(probs, k, engine=engine, maxiter=37,
                              tol=1e-30, **kw)
            assert res[0].iterations == 37
            assert_lane_equal(res[0], base[0], 0, rr=True, trace=True)


class TestDonationAndCompaction:
    """ISSUE 7: donated steppers must not invalidate harvested results;
    converged-lane compaction repacks without touching live lanes."""

    def test_harvested_results_survive_donating_steps(self):
        """harvest() hands out host copies: results collected while
        other lanes keep stepping (donating the pool state each tick)
        stay bit-stable through completion."""
        eng = SolverEngine(SolverEngineConfig(
            batch_slots=4, chunk_iters=8, donate=True, **BK))
        r_easy = eng.submit(tridiagonal_spd(128, off=-0.1))
        r_hard = eng.submit(tridiagonal_spd(400))
        while r_easy not in eng.results:
            eng.step()
        x = eng.results[r_easy].x
        assert isinstance(x, np.ndarray)         # host copy, not a view
        snap = x.copy()
        eng.run_to_completion()                  # more donating steps
        np.testing.assert_array_equal(eng.results[r_easy].x, snap)
        assert eng.results[r_hard].converged

    def test_results_independent_of_donation(self):
        """donate on/off is invisible in results — same x bitwise."""
        probs = [poisson_2d(12), tridiagonal_spd(200)]
        outs = []
        for donate in (False, True):
            eng = SolverEngine(SolverEngineConfig(
                batch_slots=2, chunk_iters=16, donate=donate, **BK))
            rids = [eng.submit(a) for a in probs]
            eng.run_to_completion()
            outs.append([eng.results[r] for r in rids])
        assert_results_bit_identical(outs[1], outs[0])

    def test_compaction_shrinks_pool_and_preserves_results(self):
        """Seven easy lanes converge early; once they harvest, the pool
        repacks the surviving lane into the smallest bucket — and the
        survivor's result is bit-identical to a never-compacting run."""
        def build(compact_fraction):
            eng = SolverEngine(SolverEngineConfig(
                batch_slots=8, chunk_iters=8,
                compact_fraction=compact_fraction, **BK))
            easies = [eng.submit(tridiagonal_spd(64 + 8 * i, off=-0.1))
                      for i in range(7)]
            hard = eng.submit(tridiagonal_spd(500))
            return eng, easies, hard

        eng, easies, hard = build(0.5)
        pool = eng._pool(None, None)
        compacted = False
        while pool.any_active:
            eng.step()
            compacted = compacted or pool.slots < 8
        assert compacted and pool.slots < 8
        assert pool.state.mem.shape[1] == pool.slots

        # compact_fraction=0 disables compaction: the reference run
        ref, ref_easies, ref_hard = build(0.0)
        ref.run_to_completion()
        assert ref._pool(None, None).slots == 8
        np.testing.assert_array_equal(
            np.asarray(eng.results[hard].x),
            np.asarray(ref.results[ref_hard].x))
        assert eng.results[hard].iterations == \
            ref.results[ref_hard].iterations
        for r, rr in zip(easies, ref_easies):
            np.testing.assert_array_equal(np.asarray(eng.results[r].x),
                                          np.asarray(ref.results[rr].x))

    def test_admission_regrows_compacted_pool(self):
        """A compacted pool grows its lane bucket back on demand: a new
        submit after compaction is admitted, not rejected."""
        eng = SolverEngine(SolverEngineConfig(
            batch_slots=8, chunk_iters=8, **BK))
        for i in range(7):
            eng.submit(tridiagonal_spd(64 + 8 * i, off=-0.1))
        hard = eng.submit(tridiagonal_spd(500))
        pool = eng._pool(None, None)
        while pool.slots == 8 and pool.any_active:
            eng.step()
        assert pool.slots < 8                     # compaction happened
        assert eng.free_slots() == 7              # capacity view intact
        late = eng.submit(tridiagonal_spd(300))
        assert pool.slots >= 2                    # lanes grew back
        eng.run_to_completion()
        assert eng.results[late].converged
        assert eng.results[hard].converged
