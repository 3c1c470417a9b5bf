"""Share of the HBM roofline that the Pallas ``spmv_ellpack`` kernel reaches.

The least time the chip could take for the window's lane-SpMVs (each
solve runs ``iterations + 1``: the warm-up ``r0 = b - A x0`` and one per
iteration), at the least bytes the algorithm needs
(:func:`bench.roofline.spmv_min_bytes` of the system, not of its padded
arrays), over the peak bandwidth of the device, divided by the summed
device time of the kernel's events in the trace.  SpMV is bound by
bytes: its two operations per nonzero never come near the FLOP/s bound.
"""
from bench.roofline import spmv_min_bytes

KERNELS = ("spmv_ellpack",)


def read(run):
    if run.trace is None:
        return None
    kernel_s = run.trace["kernel_s"].get("spmv_ellpack", 0.0)
    if kernel_s <= 0:
        return None
    widths = run.cfg["precision"]
    per_spmv = spmv_min_bytes(run.a, widths["value_bytes"],
                              widths["vector_bytes"])
    need = sum((a.iterations + 1) * per_spmv for a in run.win.answers)
    return 100.0 * need / run.peaks["hbm_bytes_per_s"] / kernel_s
