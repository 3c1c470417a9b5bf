"""Least work of a kernel, computed from the system and not from its layout.

:func:`spmv_min_bytes` is what one sparse matrix-vector product has to
move at the least: every nonzero's value at the configuration's
matrix width and its column index at the narrowest of 2 or 4 bytes
that holds the column count, one read of ``x`` and one write of ``y``
at the vector width.  Padding is never counted, so every layout is held
to the same work.
"""
from __future__ import annotations

import json
import pathlib

PEAKS = pathlib.Path(__file__).resolve().parent / "peaks.json"


def index_bytes(n_cols: int) -> int:
    return 2 if n_cols <= (1 << 15) else 4


def spmv_min_bytes(a, value_bytes: int = 2, vector_bytes: int = 4) -> int:
    n_rows, n_cols = a.shape
    return (a.nnz * (value_bytes + index_bytes(n_cols))
            + (n_cols + n_rows) * vector_bytes)


def peaks_of(device_kind: str) -> dict:
    """The device's published peaks; a device not in the table is an
    error, not a default."""
    table = json.loads(PEAKS.read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in {PEAKS}")
    return table[device_kind]
