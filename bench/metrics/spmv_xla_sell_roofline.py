"""Share of the HBM roofline that the XLA SELL SpMV reaches.

``spmv_ellpack_roofline``'s arithmetic: the least time the chip could
take for the window's lane-SpMVs (``iterations + 1`` a solve) at the
least bytes the algorithm needs (:func:`bench.roofline.spmv_min_bytes`)
over the device's peak bandwidth, divided by the device self time of
the operations in the program's ``m1_xla_sell`` scope
(``batch.batched_matvec_sell``; ``bench/programtrace.py`` reads the
scope from each operation's metadata).
"""
from bench import programtrace
from bench.roofline import spmv_min_bytes

SCOPE = "m1_xla_sell"


def read(run):
    trace = programtrace.view_of(run).trace
    scope_s = trace["scope_s"].get(SCOPE, 0.0) if trace else 0.0
    if scope_s <= 0:
        return None
    widths = run.cfg["precision"]
    per_spmv = spmv_min_bytes(run.a, widths["value_bytes"],
                              widths["vector_bytes"])
    need = sum((a.iterations + 1) * per_spmv for a in run.win.answers)
    return 100.0 * need / run.peaks["hbm_bytes_per_s"] / scope_s
