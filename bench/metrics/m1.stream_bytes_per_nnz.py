"""Bytes the M1 operand streams per lane-SpMV, per nonzero of the system.

The program's own exact counters over the window
(``bytes_streamed_est / spmv_calls``: the packed values and indices,
padding included) over the nonzeros of the configuration's operator.
The least a layout can stream is the value and index width
of one nonzero; the rest is padding.
"""


def read(run):
    c = run.win.counters
    if not c.get("spmv_calls"):
        return None
    return c["bytes_streamed_est"] / c["spmv_calls"] / run.a.nnz
