"""Benchmark driver — one section per paper table/figure.

``python -m benchmarks.run [--tier small|large|all] [--smoke]``

Every section that returns rows is also persisted as machine-readable
``BENCH_<name>.json`` at the repo root (see
:func:`benchmarks.common.write_bench_json`), so the perf trajectory is
collected across PRs — CI's smoke lane runs ``--smoke`` and uploads the
JSON files as artifacts.

``--smoke`` runs the fast, always-on subset (VSR accounting + the
batched-solver throughput/VM-overhead section with a reduced bag): a
quick signal that the numbers still materialize, not a rigorous timing.
The smoke lane doubles as three regression guards on the batched
solver: after the JSON is written it exits nonzero if ``vm_overhead``
exceeds ``benchmarks.batched_solver.VM_OVERHEAD_MAX`` (1.25, the
ISSUE-6 dispatch gap), if ``speedup`` over ``python_loop`` drops below
``benchmarks.batched_solver.SPEC_SPEEDUP_MIN`` (1.5, the ISSUE-7
batched-loop gap), or if sliced-ELL's throughput on the skewed
power-law bag falls below ``SELL_SPEEDUP_MIN`` of row-ELL's (the
ISSUE-8 layout guard — all floors are recorded in the section's JSON
``meta``).  The ``engine_health`` section adds two more (ISSUE 9): a
deliberately-singular lane must exit ``BREAKDOWN_INDEFINITE`` in fewer
than maxiter iterations, and the engine's ``bytes_streamed_est`` metric
must agree with the packed-array accounting within
``benchmarks.engine_health.BYTES_REL_ERR_MAX`` (1%).
"""
from __future__ import annotations

import argparse
import time


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--tier", default="small",
                    choices=["small", "large", "all"])
    ap.add_argument("--smoke", action="store_true",
                    help="fast subset for CI; still emits BENCH_*.json")
    args = ap.parse_args(argv)

    import jax
    jax.config.update("jax_enable_x64", True)
    from repro.compile_cache import enable_compile_cache
    print(f"# compilation cache: {enable_compile_cache()}")

    from benchmarks import (batched_solver, engine_health,
                            fig9_residual_traces, roofline_table,
                            spmv_kernel, tab4_solver_time, tab5_throughput,
                            tab7_iterations, vsr_access_counts)
    from benchmarks.common import write_bench_json

    sections = [
        ("vsr_access_counts",
         "§5.5 VSR access accounting (naive 19 -> 14 -> 13)",
         vsr_access_counts.run, {}),
        ("tab4_solver_time", "Table 4: solver time", tab4_solver_time.run,
         {"tier": args.tier}),
        ("tab5_throughput", "Table 5: throughput + fraction-of-peak",
         tab5_throughput.run, {"tier": args.tier}),
        ("tab7_iterations", "Table 7: iteration counts vs FP64",
         tab7_iterations.run, {"tier": args.tier}),
        ("fig9_residual_traces", "Fig. 9: residual traces",
         fig9_residual_traces.run, {}),
        ("spmv_kernel", "Kernel: SpMV stream bytes per scheme",
         spmv_kernel.run, {"tier": args.tier}),
        ("roofline_table", "Roofline: dry-run table (single pod)",
         roofline_table.run, {}),
        ("batched_solver",
         "Batched solver: systems/sec + stream-VM overhead",
         batched_solver.run, {"smoke": args.smoke}),
        ("engine_health",
         "Engine health: breakdown lifecycle + metrics accounting",
         engine_health.run, {"smoke": args.smoke}),
    ]
    if args.smoke:
        keep = {"vsr_access_counts", "batched_solver", "engine_health"}
        sections = [s for s in sections if s[0] in keep]

    failures = []
    for name, title, fn, kw in sections:
        print(f"\n=== {title} ===")
        t0 = time.time()
        rows = fn(**kw)
        elapsed = time.time() - t0
        if rows is not None:
            meta = {"tier": args.tier, "smoke": args.smoke,
                    "elapsed_s": round(elapsed, 2)}
            if name == "batched_solver":
                meta["vm_overhead_max"] = batched_solver.VM_OVERHEAD_MAX
                meta["spec_speedup_min"] = batched_solver.SPEC_SPEEDUP_MIN
                meta["sell_speedup_min"] = batched_solver.SELL_SPEEDUP_MIN
                meta["sell_bytes_reduction_min"] = (
                    batched_solver.SELL_BYTES_REDUCTION_MIN)
                meta["steps_per_sync"] = batched_solver.STEPS_PER_SYNC
            if name == "engine_health":
                meta["bytes_rel_err_max"] = engine_health.BYTES_REL_ERR_MAX
            write_bench_json(name, rows, meta=meta)
        print(f"--- ({elapsed:.1f}s)")
        if args.smoke:
            # Regression guards (after the JSON is persisted, so a
            # failing run still uploads its numbers as a CI artifact).
            guards = {
                "batched_solver": (batched_solver.check_vm_overhead,
                                   batched_solver.check_spec_speedup,
                                   batched_solver.check_sell_speedup),
                "engine_health": (engine_health.check_breakdown,
                                  engine_health.check_bytes),
            }.get(name, ())
            for guard in guards:
                try:
                    guard(rows)
                except SystemExit as e:
                    failures.append(str(e))

    if failures:
        for f in failures:
            print(f"FAIL: {f}")
        raise SystemExit(1)


if __name__ == "__main__":
    main()
