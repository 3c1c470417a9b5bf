#!/usr/bin/env python3
"""Sweep the offered rate of an open-loop cell to find its knee.

    python3 bench/knee.py --workload graph500_s15.stream --seed 7 \\
        --seconds 30 --rates 1,2,3,4

One process warms the cell's engine once, then offers each rate for
``--seconds`` in turn.  For each rate it prints one JSON line: requests
completed per second of the window, the latency median and 90th
percentile, the mean latency of the first and last third of the
requests, the backlog when arrivals ended, the drain after, and the three longest
host spans of each kind (``step``, ``submit``, ``wait``).  The knee
is the highest rate whose backlog does not grow: the last third waits
no longer than the first and the backlog at the close stays within one
engine's slots.  The traffic file's ``rate_per_s`` is set to 0.8 x the
knee by hand; the benchmark never searches for a rate.
"""
from __future__ import annotations

import argparse
import json
import statistics
import sys
import time

import run


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    loop = run.setup(args.workload).loop
    from bench import loadgen
    for rate in (float(r) for r in args.rates.split(",")):
        loop.traffic["rate_per_s"] = rate
        loop.prepare(args.seed, args.seconds)
        t0 = time.perf_counter()
        spans = loadgen.Spans()
        win = loop.window(args.seed, args.seconds, spans)
        lat = win.latencies
        third = max(1, len(lat) // 3)
        print(json.dumps({
            "rate_per_s": rate, "requests": win.attempted,
            "missing": win.missing,
            "completed_per_s": len(win.answers) / args.seconds,
            "latency_p50_s": loadgen.percentile(lat, 0.5),
            "latency_p90_s": loadgen.percentile(lat, 0.9),
            "first_third_mean_s": statistics.mean(lat[:third]),
            "last_third_mean_s": statistics.mean(lat[-third:]),
            "backlog_at_close": win.backlog_at_close,
            "drain_s": time.perf_counter() - t0 - args.seconds,
            "longest_s": {k: sorted(v)[-3:] for k, v in spans.seconds.items()},
            }),
            flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
