"""Median host time of one ``SolverEngine.submit`` (admission), in ms.

Taken from the benchmark's own ``submit`` span around each call: the
lane's packing into the pool, its transfer and its warm-up SpMV.
"""
import statistics


def read(run):
    spans = run.spans.seconds.get("submit")
    if not spans:
        return None
    return 1000.0 * statistics.median(spans)
