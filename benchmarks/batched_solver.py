"""Batched multi-system solver throughput + stream-VM dispatch overhead.

Four ways to solve the same bag of heterogeneous SPD systems:

* ``python_loop`` — one-by-one through ``jpcg_solve`` (one compiled loop
  per padded bucket, dispatched serially from Python);
* ``batched_phases`` — all systems in ONE compiled ``lax.while_loop``
  through the phase-fused engine (``engine="phases"``, the oracle);
* ``batched_vm`` — the same batch through the *generic* stream VM: the
  program is a traced operand dispatched word-at-a-time by
  ``lax.switch`` (``engine="vm", specialize=False``, the fallback path);
* ``batched_vm_spec`` — the *specialized* stream VM: the compiled
  paper-policy program unrolled into the executable at trace time
  (``engine="vm"``, the production default).

Reading the numbers: on a *serial CPU host* the loop generally wins —
every padded FLOP executes sequentially and the batch runs until its
slowest lane converges; the CPU batched/loop ratio is the padding +
convergence-sync overhead this benchmark tracks, and the throughput win
appears on SIMD hardware (TPU) where extra lanes occupy otherwise-idle
vector lanes.  ``vm_overhead`` (t_vm / t_phases) is the dispatch cost of
each VM path relative to the phase-fused loop for the *same arithmetic*
— both VM paths are bit-identical to phases, so any gap is pure
dispatch.  ``spec_speedup`` (t_generic_vm / t_spec_vm) is what
trace-time program specialization buys.  The production path's
``vm_overhead`` (the ``batched_vm_spec`` row) is the guarded headline:
``benchmarks/run.py --smoke`` exits nonzero when it exceeds
:data:`VM_OVERHEAD_MAX` (see :func:`check_vm_overhead`), so the
dispatch gap cannot silently regress in CI.

Each batched row also reports ``iters_per_s`` — total CG iterations
retired per second across the whole bag — ``chunk``, the
``steps_per_sync`` iteration-chunking knob the run used (ISSUE 7: k
iterations per termination sync, bit-identical for any k) — and the
layout economics (ISSUE 8): ``layout`` is the stacked layout the run
packed (``choose_layout``'s pick for the default ``layout="auto"``
rows, the explicit override for the skew rows), ``padding_ratio`` is
stored slots / nnz for that packing, and ``stream_bytes_per_nnz`` the
measured matrix-stream bytes (at-rest values + local indices, padding
included) per useful nonzero.

The ``skew_vm_rowell`` / ``skew_vm_sell`` rows time the SAME skewed
power-law bag through the specialized VM with the layout forced each
way — sliced-ELL exists for exactly this bag shape, so the smoke lane
guards that it doesn't lose throughput (:func:`check_sell_speedup`,
floor :data:`SELL_SPEEDUP_MIN`) and ``run`` asserts the headline byte
claim: mixed-V3 sliced-ELL streams ≥ :data:`SELL_BYTES_REDUCTION_MIN`
fewer bytes/nnz than FP64-at-rest row-ELL, measured from the packed
arrays.  Both layouts are bit-identical (asserted below).

The ``sharded_vm_d1`` / ``sharded_vm_d8`` rows (ISSUE 10) time the
default bag through the specialized VM with the lane axis placed on a
``lane_mesh()`` — each in a child interpreter that forces the host
device count (1 vs 8 CPU devices via ``XLA_FLAGS``), because the
parent session must keep a single device.  Lane sharding is
bit-identical by contract (asserted in-process below and property-
tested in tests/test_shard.py), so the row pair is pure throughput:
on a serial CPU host the 8-way split is bookkeeping overhead; the
ratio is the number to watch on hardware with real parallel devices.

``python -m benchmarks.batched_solver [--repeat-suite N] [--smoke]
[--overhead-threshold X] [--speedup-floor X] [--sell-floor X]``
"""
from __future__ import annotations

import json
import os
import statistics
import subprocess
import sys
import textwrap
import time

import jax
import numpy as np

from benchmarks.common import emit
from repro.core.batch import batch_cache_info, jpcg_solve_batched
from repro.core.cg import jpcg_solve
from repro.core.precision import get_scheme
from repro.core.shard import lane_mesh
from repro.sparse import (diag_dominant_spd, poisson_2d, powerlaw_spd,
                          tridiagonal_spd)
from repro.sparse.stacking import choose_layout, stack_rowell, stack_sell

HEADER = ["mode", "systems", "total_iters", "time_s", "systems_per_s",
          "iters_per_s", "chunk", "layout", "padding_ratio",
          "stream_bytes_per_nnz", "speedup", "vm_overhead",
          "spec_speedup"]

BK = dict(block_rows=8, col_tile=128)

#: Iteration-chunking knob under test — joins every batched row.
STEPS_PER_SYNC = 8

#: CI regression guard: the production (specialized) VM path may cost at
#: most this factor over the phase-fused oracle before the smoke lane
#: fails.  The steady-state target is ≤ 1.05; the guard leaves headroom
#: for noisy CI runners.
VM_OVERHEAD_MAX = 1.25

#: CI regression guard (ISSUE 7): the specialized VM path must beat the
#: python_loop baseline by at least this factor.  Steady state after the
#: row-ELL + chunking rework is ~4–6× on the smoke bag; the floor is set
#: well below that so only a structural regression (e.g. the scatter
#: SpMV creeping back, which ran at ~0.03×) trips it, not CI noise.
SPEC_SPEEDUP_MIN = 1.5

#: CI regression guard (ISSUE 8): on the skewed power-law bag —
#: sliced-ELL's home turf — the sell-packed specialized VM must be no
#: slower than the row-ELL packing (systems/s ratio ≥ this floor).
#: Steady state is ≥ 1 because sell runs strictly fewer padded slots;
#: the floor sits slightly below parity to absorb CI timer noise on a
#: bag where both paths take single-digit ms.
SELL_SPEEDUP_MIN = 0.95

#: Headline byte claim asserted by :func:`run` (ISSUE 8 acceptance):
#: mixed-V3 sliced-ELL must stream at least this fraction fewer
#: bytes/nnz than FP64-at-rest row-ELL on the skewed bag, measured
#: from the packed arrays (fp32+int16 at lower padding vs fp64+int16).
SELL_BYTES_REDUCTION_MIN = 0.40


#: Host device counts for the lane-sharded rows; each runs in a child
#: interpreter with XLA_FLAGS forcing the split (the parent session
#: stays single-device — same rule as tests/conftest.py).  Only a
#: CPU-backed parent spawns them: a parent on a chip holds the chip,
#: and a child that needs it would fail or hang.
SHARD_DEVICES = (1, 8)


def _sharded_row_times(devices: int, smoke: bool, steps_per_sync: int,
                       maxiter: int) -> dict:
    """Median sharded-solve wall time under N forced host devices,
    measured inside a child interpreter (timing excludes the child's
    startup and compile — warm-up happens before the clock starts)."""
    script = textwrap.dedent(f"""
        import os
        os.environ["XLA_FLAGS"] = (
            "--xla_force_host_platform_device_count={devices}")
        import json
        import statistics
        import time
        import jax
        jax.config.update("jax_enable_x64", True)
        from benchmarks.batched_solver import BK, _bag
        from repro.core.batch import jpcg_solve_batched
        from repro.core.shard import lane_mesh
        probs = _bag(1, smoke={smoke})
        kw = dict(tol=1e-12, maxiter={maxiter},
                  steps_per_sync={steps_per_sync}, mesh=lane_mesh(),
                  engine="vm", **BK)
        res = jpcg_solve_batched(probs, **kw)          # compile
        times = []
        for _ in range(5):
            t0 = time.perf_counter()
            res = jpcg_solve_batched(probs, **kw)
            jax.block_until_ready(res[-1].x)
            times.append(time.perf_counter() - t0)
        print(json.dumps({{
            "devices": jax.device_count(),
            "time_s": statistics.median(times),
            "iters": int(sum(r.iterations for r in res)),
            "systems": len(probs)}}))
        """)
    r = subprocess.run([sys.executable, "-c", script],
                       env=os.environ.copy(), capture_output=True,
                       text=True, timeout=1800)
    if r.returncode != 0:
        raise RuntimeError("sharded bench subprocess (devices="
                           f"{devices}) failed:\n{r.stderr[-2000:]}")
    return json.loads(r.stdout.strip().splitlines()[-1])


def _bag(copies: int = 1, smoke: bool = False):
    if smoke:
        return [poisson_2d(16), tridiagonal_spd(300),
                diag_dominant_spd(300, nnz_per_row=8, dominance=1.2,
                                  seed=1)]
    base = [
        poisson_2d(24),
        poisson_2d(30),
        tridiagonal_spd(700),
        tridiagonal_spd(900, off=-0.8),
        diag_dominant_spd(600, nnz_per_row=10, dominance=1.1, seed=1),
        diag_dominant_spd(800, nnz_per_row=12, dominance=1.15, seed=2),
        diag_dominant_spd(500, nnz_per_row=8, dominance=1.2, seed=3),
        poisson_2d(20),
    ]
    return base * copies


def _skew_bag(smoke: bool = False):
    """Power-law row-degree bag — the padding-heavy shape sliced-ELL
    targets (row-ELL pads every row to the global max width)."""
    if smoke:
        return [powerlaw_spd(512, alpha=2.1, seed=5),
                powerlaw_spd(300, alpha=2.2, seed=1),
                powerlaw_spd(400, alpha=2.0, seed=2)]
    return [powerlaw_spd(2048, alpha=2.1, seed=5),
            powerlaw_spd(1500, alpha=2.2, seed=1),
            powerlaw_spd(1024, alpha=2.0, seed=2),
            powerlaw_spd(900, alpha=2.3, seed=3)]


def _timed(fn, *args, repeats: int = 7, **kw):
    """Median wall time over ``repeats`` runs (post-warm-up the paths
    here take single-digit ms, where one-shot timing is all noise)."""
    times = []
    out = None
    for _ in range(repeats):
        t0 = time.perf_counter()
        out = fn(*args, **kw)
        sync = out[-1].x if isinstance(out, list) else out.x
        jax.block_until_ready(sync)
        times.append(time.perf_counter() - t0)
    return out, statistics.median(times)


def check_vm_overhead(rows, threshold: float = VM_OVERHEAD_MAX):
    """Raise ``SystemExit`` (nonzero) if the production VM path's
    dispatch overhead exceeds ``threshold`` — the CI regression guard."""
    spec = next(r for r in rows if r["mode"] == "batched_vm_spec")
    if spec["vm_overhead"] > threshold:
        raise SystemExit(
            f"stream-VM dispatch regression: specialized vm_overhead "
            f"{spec['vm_overhead']} > {threshold} (t_spec/t_phases); "
            "the program-specialized path must stay fused — see "
            "ARCHITECTURE.md §specialization")


def check_spec_speedup(rows, floor: float = SPEC_SPEEDUP_MIN):
    """Raise ``SystemExit`` (nonzero) if the production VM path's
    speedup over the python_loop baseline drops below ``floor`` — the
    ISSUE-7 batched-loop-gap regression guard."""
    spec = next(r for r in rows if r["mode"] == "batched_vm_spec")
    if spec["speedup"] < floor:
        raise SystemExit(
            f"batched-loop regression: specialized VM speedup "
            f"{spec['speedup']}x over python_loop is below the floor "
            f"{floor}x; the batched hot loop must stay state-update "
            "bound — see ARCHITECTURE.md §iteration-economics")


def check_sell_speedup(rows, floor: float = SELL_SPEEDUP_MIN):
    """Raise ``SystemExit`` (nonzero) if sliced-ELL loses throughput to
    row-ELL on the skewed bag — the ISSUE-8 layout regression guard."""
    sell = next(r for r in rows if r["mode"] == "skew_vm_sell")
    rowell = next(r for r in rows if r["mode"] == "skew_vm_rowell")
    ratio = sell["systems_per_s"] / rowell["systems_per_s"]
    if ratio < floor:
        raise SystemExit(
            f"sliced-ELL regression: sell/rowell throughput ratio "
            f"{ratio:.2f} on the skewed bag is below the floor {floor} "
            "(sell runs strictly fewer padded slots there) — see "
            "ARCHITECTURE.md §sparse-layouts")


def run(repeat_suite: int = 1, smoke: bool = False,
        steps_per_sync: int = STEPS_PER_SYNC):
    jax.config.update("jax_enable_x64", True)
    probs = _bag(repeat_suite, smoke=smoke)
    kw = dict(tol=1e-12, maxiter=1000 if smoke else 4000)
    bkw = dict(steps_per_sync=steps_per_sync, **kw, **BK)

    # warm-up all four paths (compile), then time
    for a in probs:
        jpcg_solve(a, **kw, **BK)
    jpcg_solve_batched(probs, engine="phases", **bkw)
    jpcg_solve_batched(probs, engine="vm", specialize=False, **bkw)
    jpcg_solve_batched(probs, engine="vm", **bkw)

    singles, t_loop = _timed(
        lambda: [jpcg_solve(a, **kw, **BK) for a in probs])
    phases, t_phases = _timed(
        jpcg_solve_batched, probs, engine="phases", **bkw)
    vm, t_vm = _timed(jpcg_solve_batched, probs, engine="vm",
                      specialize=False, **bkw)
    spec, t_spec = _timed(jpcg_solve_batched, probs, engine="vm", **bkw)

    for s, p, v, sp in zip(singles, phases, vm, spec):
        # single-solver layout (banked ELL) sums in a different fp order
        # than the batched row-ELL, so iteration parity is near, not exact
        assert abs(s.iterations - p.iterations) <= 2, "parity violated"
        for r, label in ((v, "generic VM"), (sp, "specialized VM")):
            assert r.iterations == p.iterations, f"{label}/phases parity"
            assert np.array_equal(np.asarray(r.x), np.asarray(p.x)), \
                f"{label} not bit-identical to phases engine"

    def row(mode, res, t, bag, chunk="", layout="", stacked=None,
            speedup="", vm_overhead="", spec_speedup=""):
        iters = sum(r.iterations for r in res)
        return {"mode": mode, "systems": len(bag),
                "total_iters": iters,
                "time_s": round(t, 4),
                "systems_per_s": round(len(bag) / t, 2),
                "iters_per_s": round(iters / t, 1),
                "chunk": chunk,
                "layout": layout,
                "padding_ratio": (f"{stacked.padding_ratio:.3f}"
                                  if stacked is not None else ""),
                "stream_bytes_per_nnz": (
                    f"{stacked.stream_bytes_per_nnz():.2f}"
                    if stacked is not None else ""),
                "speedup": speedup,
                "vm_overhead": vm_overhead,
                "spec_speedup": spec_speedup}

    # the batched rows above all packed layout="auto"; measure what the
    # heuristic actually chose for this bag (at the default scheme)
    sch = get_scheme("mixed_v3")
    chosen = choose_layout(probs, default="rowell")
    stack = stack_sell if chosen == "sell" else stack_rowell
    st = stack(probs, scheme=sch)

    k = steps_per_sync
    rows = [
        row("python_loop", singles, t_loop, probs,
            speedup=round(t_loop / t_loop, 2)),
        row("batched_phases", phases, t_phases, probs, chunk=k,
            layout=chosen, stacked=st, speedup=round(t_loop / t_phases, 2)),
        row("batched_vm", vm, t_vm, probs, chunk=k,
            layout=chosen, stacked=st, speedup=round(t_loop / t_vm, 2),
            vm_overhead=round(t_vm / t_phases, 2)),
        row("batched_vm_spec", spec, t_spec, probs, chunk=k,
            layout=chosen, stacked=st, speedup=round(t_loop / t_spec, 2),
            vm_overhead=round(t_spec / t_phases, 2),
            spec_speedup=round(t_vm / t_spec, 2)),
    ]

    # --- ISSUE 8: skewed bag, row-ELL vs sliced-ELL head-to-head -----
    skew = _skew_bag(smoke=smoke)
    assert choose_layout(skew) == "sell", \
        "skew bag no longer trips the padding-ratio heuristic"
    skw = dict(steps_per_sync=steps_per_sync, **kw, **BK)
    jpcg_solve_batched(skew, engine="vm", layout="rowell", **skw)
    jpcg_solve_batched(skew, engine="vm", layout="sell", **skw)
    srow, t_srow = _timed(jpcg_solve_batched, skew, engine="vm",
                          layout="rowell", **skw)
    ssell, t_ssell = _timed(jpcg_solve_batched, skew, engine="vm",
                            layout="sell", **skw)
    for r, s in zip(srow, ssell):
        assert r.iterations == s.iterations, "sell/rowell parity"
        assert np.array_equal(np.asarray(r.x), np.asarray(s.x)), \
            "sliced-ELL not bit-identical to row-ELL"

    st_row = stack_rowell(skew, scheme=sch)
    st_sell = stack_sell(skew, scheme=sch)
    rows += [
        row("skew_vm_rowell", srow, t_srow, skew, chunk=k,
            layout="rowell", stacked=st_row),
        row("skew_vm_sell", ssell, t_ssell, skew, chunk=k,
            layout="sell", stacked=st_sell),
    ]

    # headline byte claim (ISSUE 8 acceptance): mixed-V3 at rest in
    # sliced-ELL vs FP64-at-rest row-ELL, measured from packed arrays
    st_fp64 = stack_rowell(skew, scheme=get_scheme("fp64"))
    reduction = 1 - (st_sell.stream_bytes_per_nnz()
                     / st_fp64.stream_bytes_per_nnz())
    print(f"# skew bag stream bytes/nnz: fp64 rowell "
          f"{st_fp64.stream_bytes_per_nnz():.2f} -> mixed_v3 sell "
          f"{st_sell.stream_bytes_per_nnz():.2f} "
          f"({reduction:.0%} reduction)")
    assert reduction >= SELL_BYTES_REDUCTION_MIN, (
        f"mixed_v3 sliced-ELL byte reduction {reduction:.0%} below the "
        f"{SELL_BYTES_REDUCTION_MIN:.0%} floor")

    # --- ISSUE 10: lane-sharded rows (forced host device counts) -----
    # contract check first: on this session's single device, placing the
    # lane axis on a mesh must be bitwise invisible vs the spec run
    shard = jpcg_solve_batched(probs, engine="vm", mesh=lane_mesh(), **bkw)
    for r, p in zip(shard, spec):
        assert r.iterations == p.iterations, "sharded/spec parity"
        assert np.array_equal(np.asarray(r.x), np.asarray(p.x)), \
            "lane-sharded run not bit-identical to unsharded VM"
    backend = jax.default_backend()
    if backend != "cpu":
        print(f"# sharded_vm rows not run: they spawn forced-CPU child "
              f"interpreters, which this process may not do while it "
              f"holds the {backend} device")
    for d in SHARD_DEVICES if backend == "cpu" else ():
        info = _sharded_row_times(d, smoke, steps_per_sync, kw["maxiter"])
        t = info["time_s"]
        rows.append({"mode": f"sharded_vm_d{info['devices']}",
                     "systems": info["systems"],
                     "total_iters": info["iters"],
                     "time_s": round(t, 4),
                     "systems_per_s": round(info["systems"] / t, 2),
                     "iters_per_s": round(info["iters"] / t, 1),
                     "chunk": k, "layout": chosen,
                     "padding_ratio": "", "stream_bytes_per_nnz": "",
                     "speedup": "", "vm_overhead": "",
                     "spec_speedup": ""})

    emit(rows, HEADER)
    print(f"# batch compile cache: {batch_cache_info()}")
    return rows


if __name__ == "__main__":
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--repeat-suite", type=int, default=1)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--steps-per-sync", type=int, default=STEPS_PER_SYNC,
                    help="iterations per termination sync (bit-identical "
                         "for any value; joins the 'chunk' column)")
    ap.add_argument("--overhead-threshold", type=float, default=None,
                    help="fail (exit nonzero) if the specialized path's "
                         "vm_overhead exceeds this (CI uses "
                         f"{VM_OVERHEAD_MAX})")
    ap.add_argument("--speedup-floor", type=float, default=None,
                    help="fail (exit nonzero) if the specialized path's "
                         "speedup over python_loop drops below this (CI "
                         f"uses {SPEC_SPEEDUP_MIN})")
    ap.add_argument("--sell-floor", type=float, default=None,
                    help="fail (exit nonzero) if sliced-ELL's systems/s "
                         "on the skewed bag falls below this fraction of "
                         f"row-ELL's (CI uses {SELL_SPEEDUP_MIN})")
    args = ap.parse_args()
    out = run(repeat_suite=args.repeat_suite, smoke=args.smoke,
              steps_per_sync=args.steps_per_sync)
    if args.overhead_threshold is not None:
        check_vm_overhead(out, args.overhead_threshold)
    if args.speedup_floor is not None:
        check_spec_speedup(out, args.speedup_floor)
    if args.sell_floor is not None:
        check_sell_speedup(out, args.sell_floor)
