"""Share of the HBM roofline that the Pallas ``spmv_ellpack_df`` kernel reaches.

As ``spmv_ellpack_roofline.py``, for the double-fp32 kernel: each solve
runs ``iterations + 1`` lane-SpMVs, and each residual replacement of the
window one more (the program's ``replacements`` counter), each at the
least bytes of the system at the configuration's widths
(:func:`bench.roofline.spmv_min_bytes`: 2 B values, 8 B vector
elements), over the peak bandwidth of the device and the kernel's summed
device time in the trace.  The first SpMV of a solve (``b - A x0``) and
each replacement's read ``x`` itself, at ``solution_bytes`` (12 B: x is
three fp32 words), so each of those counts the extra words of ``x``.
"""
from bench.roofline import spmv_min_bytes

KERNELS = ("spmv_ellpack_df",)


def read(run):
    if run.trace is None:
        return None
    kernel_s = run.trace["kernel_s"].get("spmv_ellpack_df", 0.0)
    if kernel_s <= 0:
        return None
    widths = run.cfg["precision"]
    per_spmv = spmv_min_bytes(run.a, widths["value_bytes"],
                              widths["vector_bytes"])
    x_extra = run.a.shape[1] * (widths.get("solution_bytes",
                                           widths["vector_bytes"])
                                - widths["vector_bytes"])
    of_x = len(run.win.answers) + run.win.counters.get("replacements", 0)
    spmvs = sum(a.iterations for a in run.win.answers) + of_x
    total = spmvs * per_spmv + of_x * x_extra
    return 100.0 * total / run.peaks["hbm_bytes_per_s"] / kernel_s
