#!/usr/bin/env python3
"""Readings that set a cell's correctness limit: the program and its control.

    python3 bench/readings.py --workload graph500_s15.bag8 --seconds 40 \\
        --seeds 101,102,... --control-seeds 201,202,203

In one process: the cell as configured (``tpu_v3``) on every seed of
``--seeds``, then the control -- the same cell with the program's own
lower-precision path switched on, ``tpu_v1`` (bfloat16 ``x`` into the
SpMV) -- on every seed of ``--control-seeds``.  Each reading is one
window of the cell's traffic at its own load, compared with the float64
reference exactly as a run compares it; one JSON line per reading gives
the numbers compared.  The lower reading is the largest residual the
program gives, the upper the smallest the control gives; the limit in
``bench/configs/<config>.json`` lies between them (see ``PERF.md``).
"""
from __future__ import annotations

import argparse
import gc
import json
import math
import sys

import run

CONTROL_SCHEME = "tpu_v1"


def readings(workload: str, seeds, seconds: float, overrides=None):
    """Yield ``(seed, Check)`` for each seed, from one warm set-up."""
    built = run.setup(workload, overrides=overrides)
    from bench import loadgen, reference
    for seed in seeds:
        built.loop.prepare(seed, seconds)
        win = built.loop.window(seed, seconds, loadgen.Spans())
        check = reference.Check(math.inf)
        for ans in win.answers:
            check.add(built.a, ans.b, ans.x, ans.status)
        check.missing(win.missing)
        yield seed, check, win
    built.loop.close()
    del built
    gc.collect()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    args = ap.parse_args(argv)
    runs = [("program", None, args.seeds),
            ("control", {"cfg": {"solver": {"scheme": CONTROL_SCHEME}}},
             args.control_seeds)]
    for side, overrides, seeds in runs:
        seeds = [int(s) for s in seeds.split(",") if s]
        if not seeds:
            continue
        for seed, check, win in readings(args.workload, seeds, args.seconds,
                                         overrides):
            print(json.dumps({"side": side, "workload": args.workload,
                              "seed": seed, "residual": check.worst,
                              "unconverged": check.unconverged,
                              "checked": check.checked,
                              "attempted": win.attempted}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
