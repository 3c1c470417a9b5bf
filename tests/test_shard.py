"""Lane-sharded multi-device solves (ISSUE 10).

The tentpole contract: placing the lane axis ``[G, n]`` on a
``jax.sharding.Mesh`` is **invisible in the bits** — every observable
(per-lane x, iteration count, final ‖r‖², residual trace, structured
exit status) of a sharded solve is bitwise identical to the unsharded
run, for every scheme × layout × engine × chunking, including bags
whose lanes converge, break down, or exhaust ``maxiter`` mid-chunk on
*different* shards.  All lane math is lane-elementwise and the one
cross-lane reduction (the ``any(active)`` sync) is a deterministic
boolean OR, so sharding must cost nothing — these tests pin that down.

Two coverage tiers, honoring the conftest rule that the main session
keeps a single CPU device:

* **in-process** tests build a mesh over all *visible* devices — 1 in
  the default session (the sharded code path, placement, padding and
  cache keys are still fully exercised), 8 in CI's ``distributed``
  lane (``XLA_FLAGS=--xla_force_host_platform_device_count=8``,
  selected with ``-m distributed``);
* **subprocess** tests force 8 host devices regardless of the parent
  session, so tier-1 always proves true multi-device bit-identity and
  the mesh-size cache economics (marked ``slow``: they recompile the
  world in a child interpreter).
"""
import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from repro.core.batch import jpcg_solve_batched
from repro.core.shard import (lane_mesh, mesh_shards, mesh_signature,
                              pad_lanes)
from repro.serve.solver_engine import SolverEngine, SolverEngineConfig
from repro.sparse import csr_from_coo, random_spd, tridiagonal_spd
from tests._hyp import given, settings, strategies as st
from tests.oracles import assert_results_bit_identical, assert_statuses

pytestmark = pytest.mark.distributed

BK = dict(block_rows=8, col_tile=128)
#: the four faithful schemes (FP64 vector file — exactly reproducible).
SCHEMES = ("fp64", "mixed_v1", "mixed_v2", "mixed_v3")
SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def _singular_J(n):
    """All-ones rank-1 matrix + sum-zero rhs: pAp = 0 on the first tick
    in any float width -> BREAKDOWN_INDEFINITE."""
    i = np.repeat(np.arange(n), n)
    j = np.tile(np.arange(n), n)
    a = csr_from_coo(i, j, np.ones(n * n), (n, n))
    b = np.zeros(n)
    b[0], b[1] = 1.0, -1.0
    return a, b


#: the bag below is solved with maxiter=MAXITER — deliberately NOT a
#: multiple of steps_per_sync=8, so budget exits land mid-chunk.
MAXITER = 11


def _mixed_fate_bag(n, seed):
    """5 lanes whose fates diverge mid-chunk (and, on a real mesh, on
    different shards): converge fast, exhaust maxiter, break down
    indefinite, run long, break down non-finite."""
    sing_a, sing_b = _singular_J(n)
    nan_b = np.ones(n)
    nan_b[0] = np.nan
    probs = [tridiagonal_spd(n, off=-0.1),        # CONVERGED (~4 ticks)
             random_spd(n, cond=1e6, seed=seed + 1),   # MAXITER (1e-30)
             sing_a,                              # BREAKDOWN_INDEFINITE
             random_spd(n, cond=50.0, seed=seed),  # runs long
             tridiagonal_spd(n)]                  # BREAKDOWN_NONFINITE
    bs = [np.ones(n), np.ones(n), sing_b, np.ones(n), nan_b]
    tols = [1e-10, 1e-30, 1e-10, 1e-10, 1e-10]
    return probs, bs, tols


EXPECTED = {1: "MAXITER", 2: "BREAKDOWN_INDEFINITE",
            4: "BREAKDOWN_NONFINITE"}


class TestShardedBitIdentity:
    """∀ scheme × layout × engine × chunking: mesh placement is
    bitwise invisible, mixed lane fates included."""

    @settings(max_examples=8, deadline=None)
    @given(scheme=st.sampled_from(SCHEMES),
           layout=st.sampled_from(["rowell", "sell"]),
           sps=st.sampled_from([1, 8]),
           engine=st.sampled_from(["vm", "phases"]),
           n=st.sampled_from([16, 24]), seed=st.integers(0, 2**16))
    def test_sharded_equals_unsharded_property(self, scheme, layout, sps,
                                               engine, n, seed):
        probs, bs, tols = _mixed_fate_bag(n, seed)
        kw = dict(tol=tols, maxiter=MAXITER, scheme=scheme,
                  layout=layout, engine=engine, steps_per_sync=sps,
                  with_trace=True, **BK)
        ref = jpcg_solve_batched(probs, bs, **kw)
        got = jpcg_solve_batched(probs, bs, mesh=lane_mesh(), **kw)
        assert_statuses(ref, EXPECTED, healthy=(0,), maxiter=100)
        assert_results_bit_identical(got, ref, rr=True, trace=True,
                                     status=True)

    def test_generic_vm_path_sharded(self):
        """The traced-program (specialize=False) VM path shards too."""
        probs, bs, tols = _mixed_fate_bag(16, seed=3)
        kw = dict(tol=tols, maxiter=MAXITER, specialize=False, **BK)
        ref = jpcg_solve_batched(probs, bs, **kw)
        got = jpcg_solve_batched(probs, bs, mesh=lane_mesh(), **kw)
        assert_results_bit_identical(got, ref, rr=True, status=True)

    def test_lane_padding_is_invisible(self):
        """G not divisible by the shard count pads with inert identity
        lanes — the result list and the metrics see only the real G."""
        from repro.core.metrics import reset_solver_metrics, solver_metrics
        probs, bs, tols = _mixed_fate_bag(16, seed=1)
        mesh = lane_mesh()
        assert pad_lanes(len(probs), mesh) % mesh_shards(mesh) == 0
        reset_solver_metrics()
        try:
            res = jpcg_solve_batched(probs, bs, tol=tols,
                                     maxiter=MAXITER, mesh=mesh, **BK)
            assert len(res) == len(probs)
            m = solver_metrics().snapshot()
            assert m["lanes"] == len(probs)
            assert sum(m["exit_status"].values()) == len(probs)
            # Pallas ELLPACK packs each distinct operator once; the
            # identity padding lanes share one more
            a, b = random_spd(16, cond=50.0, seed=1), tridiagonal_spd(24)
            kw = dict(tol=1e-10, maxiter=50, scheme="tpu_v3",
                      backend="pallas", layout="ellpack", **BK)
            reset_solver_metrics()
            got = jpcg_solve_batched([a, a, b], mesh=mesh, **kw)
            padded = pad_lanes(3, mesh) > 3
            assert solver_metrics().get("operands_packed") == 2 + padded
            ref = jpcg_solve_batched([a, a, b], **kw)
            assert_results_bit_identical(got, ref, rr=True, status=True)
        finally:
            reset_solver_metrics()

    def test_identity_padding_is_one_more_operator(self):
        """On two devices a Pallas ELLPACK bag [A, A, B] pads to four
        lanes: A, B and the identity padding are its three distinct
        operators, each packed once, and every observable is
        bit-identical to the same sharded solve with one pack per lane.
        (Against the unsharded solve this path is not bit-identical on
        every host's XLA:CPU, with or without shared packing.)"""
        out = _run("""
            import repro.core.batch as batch
            from repro.core.metrics import solver_metrics
            from repro.core.shard import lane_mesh
            from repro.sparse import random_spd, tridiagonal_spd
            a, b = random_spd(16, cond=50.0, seed=1), tridiagonal_spd(24)
            kw = dict(tol=1e-10, maxiter=50, scheme="tpu_v3",
                      backend="pallas", layout="ellpack", block_rows=8,
                      col_tile=128, mesh=lane_mesh())
            m = solver_metrics()
            got = batch.jpcg_solve_batched([a, a, b], **kw)
            shared = m.get("operands_packed")
            batch._distinct_operators = lambda csrs: (
                list(csrs), tuple(range(len(csrs))))
            ref = batch.jpcg_solve_batched([a, a, b], **kw)
            print(json.dumps({
                "devices": jax.device_count(),
                "operands": [shared, m.get("operands_packed") - shared],
                "statuses": [r.status for r in got],
                "same": all(lanes_equal(r, o) for r, o in zip(got, ref)),
                "lanes": len(got)}))
        """, devices=2, prelude=_BAG_SRC)
        assert out["devices"] == 2
        assert out["operands"] == [3, 4]
        assert out["lanes"] == 3
        assert out["statuses"] == ["CONVERGED"] * 3
        assert out["same"]

    def test_sharded_engine_matches_unsharded(self):
        """A sharded SolverEngine serving mixed-fate requests harvests
        bit-identical results and the exact same exit histogram."""
        def drive(mesh):
            eng = SolverEngine(SolverEngineConfig(
                batch_slots=8, chunk_iters=8, mesh=mesh, **BK))
            probs, bs, tols = _mixed_fate_bag(16, seed=5)
            rids = [eng.submit(a, b, tol=t, maxiter=MAXITER)
                    for a, b, t in zip(probs, bs, tols)]
            eng.run_to_completion()
            return [eng.results[r] for r in rids], eng.metrics()

        ref, m_ref = drive(None)
        got, m_got = drive(lane_mesh())
        assert_results_bit_identical(got, ref, status=True)
        assert m_got["exit_status"] == m_ref["exit_status"]
        assert m_got["admits"] == m_ref["admits"] == 5
        assert m_got["harvests"] == 5

    def test_mesh_signature_splits_executable_key(self):
        """Cache economics, tier-1 face: unsharded and every mesh size
        produce distinct keys — a 1-device mesh is NOT the unsharded
        executable (placement differs), and sizes never collide."""
        from repro.core.compile import executable_key
        base = dict(backend="xla", scheme="mixed_v3", bucket=(256, 8),
                    layout="rowell", index_bytes=2, steps_per_sync=8,
                    donate=False, interpret=False)
        sigs = [None, (("lanes", 1),), (("lanes", 2),), (("lanes", 8),)]
        keys = {executable_key("stepper", mesh=s, **base) for s in sigs}
        assert len(keys) == len(sigs)
        assert mesh_signature(None) is None
        assert mesh_signature(lane_mesh()) == \
            (("lanes", mesh_shards(lane_mesh())),)


class TestShardedSoak:
    """Satellite: a seeded ~200-tick randomized soak against a sharded
    engine — admissions, steps, harvests, compactions and bucket growth
    interleave; every request terminates classified and the metrics
    balance exactly."""

    KINDS = ("easy", "hard", "budget", "singular", "nonfinite")
    WANT = {"easy": "CONVERGED", "hard": "CONVERGED",
            "budget": "MAXITER", "singular": "BREAKDOWN_INDEFINITE",
            "nonfinite": "BREAKDOWN_NONFINITE"}

    def _submit(self, eng, rng, k):
        kind = self.KINDS[int(rng.integers(0, len(self.KINDS)))]
        # sizes straddle a bucket edge (16 vs 24->32) so admissions
        # keep forcing mid-flight bucket growth after compactions
        n = int(rng.choice([16, 24]))
        if kind == "easy":
            rid = eng.submit(tridiagonal_spd(n, off=-0.1), np.ones(n),
                             tol=1e-10, maxiter=500)
        elif kind == "hard":
            rid = eng.submit(random_spd(n, cond=100.0, seed=k),
                             np.ones(n), tol=1e-10, maxiter=500)
        elif kind == "budget":
            rid = eng.submit(tridiagonal_spd(n), np.ones(n),
                             tol=1e-30, maxiter=3)
        elif kind == "singular":
            a, b = _singular_J(n)
            rid = eng.submit(a, b, tol=1e-10, maxiter=500)
        else:
            a = tridiagonal_spd(n)
            b = np.ones(n)
            b[0] = np.nan
            rid = eng.submit(a, b, tol=1e-10, maxiter=500)
        return rid, kind

    @pytest.mark.slow
    def test_soak_200_ticks(self):
        rng = np.random.default_rng(20260808)
        eng = SolverEngine(SolverEngineConfig(
            batch_slots=8, chunk_iters=4, compact_fraction=0.75,
            mesh=lane_mesh(), **BK))
        kinds = {}
        for tick in range(200):
            if rng.random() < 0.4 and eng.free_slots() > 0:
                rid, kind = self._submit(eng, rng, tick)
                kinds[rid] = kind
            eng.step()
        eng.run_to_completion()

        assert kinds, "soak admitted nothing — broken driver"
        assert set(eng.results) == set(kinds)
        hist = {}
        for rid, kind in kinds.items():
            res = eng.results[rid]
            want = self.WANT[kind]
            assert res.status == want, (kind, res.status)
            assert res.converged == (want == "CONVERGED")
            hist[want] = hist.get(want, 0) + 1

        m = eng.metrics()
        n_req = len(kinds)
        assert m["admits"] == n_req
        assert m["harvests"] == n_req
        assert m.get("escalations", 0) == 0
        assert m["exit_status"] == hist
        assert sum(m["exit_status"].values()) == n_req
        for p in m["pools"].values():
            assert p["occupied"] == 0 and p["active"] == 0
            assert p["shards"] == mesh_shards(lane_mesh())


# --------------------------------------------------- 8-device subprocess
def _run(body: str, devices: int = 8, prelude: str = "") -> dict:
    """Run a snippet under N forced host devices; it must print JSON.
    (Subprocess per the conftest rule: the main session stays at one
    device; see tests/test_distributed.py for the same idiom.)
    ``prelude`` is prepended already-dedented (module-level helpers)."""
    snippet = prelude + textwrap.dedent(body)
    script = textwrap.dedent(f"""
        import os
        os.environ["XLA_FLAGS"] = \\
            "--xla_force_host_platform_device_count={devices}"
        import json
        import jax
        jax.config.update("jax_enable_x64", True)
        import numpy as np
        import jax.numpy as jnp
        {textwrap.indent(snippet, '        ').strip()}
        """)
    env = dict(os.environ, PYTHONPATH=SRC)
    r = subprocess.run([sys.executable, "-c", script], env=env,
                       capture_output=True, text=True, timeout=900)
    assert r.returncode == 0, r.stderr[-4000:]
    return json.loads(r.stdout.strip().splitlines()[-1])


_BAG_SRC = '''
from repro.sparse import csr_from_coo, random_spd, tridiagonal_spd

def mixed_fate_bag(n, seed):
    i = np.repeat(np.arange(n), n); j = np.tile(np.arange(n), n)
    sing_a = csr_from_coo(i, j, np.ones(n * n), (n, n))
    sing_b = np.zeros(n); sing_b[0], sing_b[1] = 1.0, -1.0
    nan_b = np.ones(n); nan_b[0] = np.nan
    probs = [tridiagonal_spd(n, off=-0.1),
             random_spd(n, cond=1e6, seed=seed + 1), sing_a,
             random_spd(n, cond=50.0, seed=seed), tridiagonal_spd(n)]
    bs = [np.ones(n), np.ones(n), sing_b, np.ones(n), nan_b]
    return probs, bs, [1e-10, 1e-30, 1e-10, 1e-10, 1e-10]

def _eq(a, b):
    # NaN-tolerant bitwise compare; engine results carry
    # residual_trace=None, where equal_nan would choke on isnan
    a, b = np.asarray(a), np.asarray(b)
    nan_ok = a.dtype.kind == "f" and b.dtype.kind == "f"
    return np.array_equal(a, b, equal_nan=nan_ok)

def lanes_equal(r, o):
    return (r.iterations == o.iterations and r.status == o.status
            and _eq(r.rr, o.rr) and _eq(r.x, o.x)
            and _eq(r.residual_trace, o.residual_trace))
'''


@pytest.mark.slow                 # subprocess + 8 host devices
class TestEightDevices:
    def test_bit_identity_8dev(self):
        """True 8-device run: G=5 pads to 8, one lane per shard, every
        observable bit-identical to the unsharded solve across scheme ×
        layout × chunking × engine."""
        out = _run("""
            from repro.core.batch import jpcg_solve_batched
            from repro.core.shard import lane_mesh
            mesh = lane_mesh()
            probs, bs, tols = mixed_fate_bag(16, seed=7)
            detail = []
            for scheme in ("fp64", "mixed_v3"):
                for layout in ("rowell", "sell"):
                    for engine, sps in (("vm", 1), ("vm", 8),
                                        ("phases", 8)):
                        kw = dict(tol=tols, maxiter=11, scheme=scheme,
                                  layout=layout, engine=engine,
                                  steps_per_sync=sps, with_trace=True,
                                  block_rows=8, col_tile=128)
                        ref = jpcg_solve_batched(probs, bs, **kw)
                        got = jpcg_solve_batched(probs, bs, mesh=mesh,
                                                 **kw)
                        same = len(got) == len(ref) and all(
                            lanes_equal(r, o) for r, o in zip(got, ref))
                        detail.append([scheme, layout, engine, sps,
                                       bool(same)])
            print(json.dumps({"devices": jax.device_count(),
                              "detail": detail}))
        """, prelude=_BAG_SRC)
        assert out["devices"] == 8
        bad = [d for d in out["detail"] if not d[-1]]
        assert not bad, f"sharded run not bit-identical: {bad}"

    def test_engine_8dev_matches_unsharded(self):
        """Sharded SolverEngine on 8 real devices: bit-identical
        harvests, identical exit histogram, device-local compaction."""
        out = _run("""
            from repro.serve.solver_engine import (SolverEngine,
                                                   SolverEngineConfig)
            from repro.core.shard import lane_mesh

            def drive(mesh):
                eng = SolverEngine(SolverEngineConfig(
                    batch_slots=8, chunk_iters=8, mesh=mesh,
                    block_rows=8, col_tile=128))
                probs, bs, tols = mixed_fate_bag(16, seed=5)
                rids = [eng.submit(a, b, tol=t, maxiter=11)
                        for a, b, t in zip(probs, bs, tols)]
                eng.run_to_completion()
                return ([eng.results[r] for r in rids], eng.metrics())

            ref, m_ref = drive(None)
            got, m_got = drive(lane_mesh())
            same = all(lanes_equal(r, o) for r, o in zip(got, ref))
            shards = [p["shards"] for p in m_got["pools"].values()]
            print(json.dumps({"devices": jax.device_count(),
                              "same": bool(same),
                              "hist_equal": m_got["exit_status"] ==
                                            m_ref["exit_status"],
                              "shards": shards}))
        """, prelude=_BAG_SRC)
        assert out["devices"] == 8
        assert out["same"] and out["hist_equal"]
        assert out["shards"] == [8]

    def test_cache_economics_mesh_sizes(self):
        """Satellite: mesh sizes {1, 2, 8} are three distinct
        executables — one compile each (a repeat is a pure cache hit,
        no retrace), and none collide with each other."""
        out = _run("""
            from repro.core.batch import (batch_cache_clear,
                                          batch_cache_info,
                                          jpcg_solve_batched)
            from repro.core.shard import lane_mesh
            from repro.core.vm import vm_executable_stats
            from repro.sparse import tridiagonal_spd
            devs = jax.devices()
            # G=8: divisible by every mesh size, so the lane bucket is
            # identical everywhere — only the mesh field distinguishes
            probs = [tridiagonal_spd(16 + 2 * g) for g in range(8)]
            batch_cache_clear()
            seq = []
            for d in (1, 2, 8):
                mesh = lane_mesh(devs[:d])
                for _ in range(2):
                    jpcg_solve_batched(probs, tol=1e-10, maxiter=20,
                                       mesh=mesh, block_rows=8,
                                       col_tile=128)
                info = batch_cache_info()
                seq.append([d, info["entries"], info["misses"],
                            info["hits"], vm_executable_stats()["traces"]])
            print(json.dumps({"seq": seq}))
        """)
        entries = [row[1] for row in out["seq"]]
        misses = [row[2] for row in out["seq"]]
        hits = [row[3] for row in out["seq"]]
        traces = [row[4] for row in out["seq"]]
        # one new entry + one miss per mesh size; the repeat is a hit
        assert entries == [1, 2, 3]
        assert misses == [1, 2, 3]
        assert hits == [1, 2, 3]
        # exactly one jit trace per mesh size — the repeat retraced
        # nothing (no silent double compile behind the key)
        assert traces[0] >= 1
        assert traces[1] == traces[0] + 1
        assert traces[2] == traces[1] + 1
