"""Jitted wrappers around the Pallas kernels — the `backend="pallas"` path.

* :func:`ell_operator_pallas` (alias ``bell_operator_pallas``) wraps a
  sparse matrix as an operator whose ``matvec`` is the
  :mod:`repro.kernels.spmv` kernel (banked-ELLPACK, mixed precision).
* :func:`make_phase_ops` returns the fused phase-2/phase-3/dot kernels in
  the signature :func:`repro.core.phases.jpcg_loop` consumes, so the whole
  JPCG loop body runs as three Pallas kernels per iteration — the paper's
  three phases, one kernel each.

``interpret=None`` resolves through :func:`default_interpret`: the Pallas
interpreter on the CPU backend only (the test suite), Mosaic everywhere
else.  Nothing catches a Mosaic error and retries interpreted or on the
XLA matvec: a kernel that does not compile for the device fails there.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.precision import PrecisionScheme, get_scheme
from repro.kernels.dot import dot_pallas, dot3_pallas
from repro.kernels.fused_phase import phase2_pallas, phase3_pallas
from repro.kernels.spmv import spmv_pallas
from repro.sparse.csr import CSRMatrix
from repro.sparse.ellpack import EllpackMatrix, csr_to_ellpack

__all__ = ["PallasEllOperator", "ell_operator_pallas", "bell_operator_pallas",
           "make_phase_ops", "default_interpret"]


def default_interpret() -> bool:
    """Interpret on the CPU backend only; compile for any device."""
    return jax.default_backend() == "cpu"


@dataclasses.dataclass(frozen=True)
class PallasEllOperator:
    """ELLPACK matrix whose matvec is the Pallas SpMV kernel."""

    tile_cols: jax.Array   # int32[B, T]
    vals: jax.Array        # matrix_dtype[B, T, E, R]
    local_cols: jax.Array  # int32[B, T, E, R]
    diag: jax.Array        # vector_dtype[n]
    n: int
    block_rows: int
    col_tile: int
    padded_cols: int
    scheme: PrecisionScheme
    nnz: int
    interpret: bool

    @classmethod
    def from_ellpack(cls, m: EllpackMatrix, scheme, diag,
                     interpret: bool | None = None) -> "PallasEllOperator":
        scheme = get_scheme(scheme)
        if interpret is None:
            interpret = default_interpret()
        return cls(
            tile_cols=jnp.asarray(m.tile_cols),
            vals=jnp.asarray(m.vals).astype(scheme.matrix_dtype),
            local_cols=jnp.asarray(m.local_cols),
            diag=jnp.asarray(diag).astype(scheme.vector_dtype),
            n=m.shape[0], block_rows=m.block_rows, col_tile=m.col_tile,
            padded_cols=m.padded_cols, scheme=scheme, nnz=m.nnz,
            interpret=interpret)

    def matvec(self, x: jax.Array) -> jax.Array:
        x_pad = jnp.zeros(self.padded_cols, x.dtype).at[: self.n].set(x)
        x_tiles = x_pad.reshape(-1, self.col_tile)
        y = spmv_pallas(self.tile_cols, self.vals, self.local_cols, x_tiles,
                        scheme=self.scheme, interpret=self.interpret)
        return y.reshape(-1)[: self.n].astype(self.scheme.vector_dtype)

    def flops_per_matvec(self) -> int:
        return 2 * self.nnz


jax.tree_util.register_dataclass(
    PallasEllOperator,
    data_fields=["tile_cols", "vals", "local_cols", "diag"],
    meta_fields=["n", "block_rows", "col_tile", "padded_cols", "scheme",
                 "nnz", "interpret"])


def ell_operator_pallas(a, scheme, *, diag=None, block_rows: int = 256,
                        col_tile: int = 512,
                        interpret: bool | None = None) -> PallasEllOperator:
    """Coerce CSR / EllpackMatrix to a Pallas-backed operator."""
    scheme = get_scheme(scheme)
    if isinstance(a, PallasEllOperator):
        return a
    if isinstance(a, CSRMatrix):
        d = a.diagonal() if diag is None else diag
        m = csr_to_ellpack(a, block_rows=block_rows, col_tile=col_tile)
        return PallasEllOperator.from_ellpack(m, scheme, d, interpret)
    if isinstance(a, EllpackMatrix):
        if diag is None:
            raise ValueError("EllpackMatrix input requires an explicit diag")
        return PallasEllOperator.from_ellpack(a, scheme, diag, interpret)
    arr = np.asarray(a)
    if arr.ndim == 2 and arr.shape[0] == arr.shape[1]:
        from repro.sparse.csr import csr_from_coo
        rows, cols = np.nonzero(arr)
        csr = csr_from_coo(rows, cols, arr[rows, cols], arr.shape)
        return ell_operator_pallas(csr, scheme, diag=diag,
                                   block_rows=block_rows, col_tile=col_tile,
                                   interpret=interpret)
    raise TypeError(f"cannot build a Pallas operator from {type(a)}")


#: cg.py historical alias.
bell_operator_pallas = ell_operator_pallas


def make_phase_ops(interpret: bool | None = None):
    """Phase-op triple for :func:`repro.core.phases.jpcg_loop`.

    Returns ``(dot, phase2, phase3)`` where
    ``dot(a, b) -> scalar``, ``phase2(alpha, r, ap, diag) -> (r', [rr, rz])``
    and ``phase3(alpha, beta, r', diag, p, x) -> (p', x')`` — each one a
    single fused Pallas kernel.
    """
    if interpret is None:
        interpret = default_interpret()

    def dot(a, b):
        return dot_pallas(a, b, acc_dtype=a.dtype, interpret=interpret)

    def phase2(alpha, r, ap, diag):
        return phase2_pallas(alpha, r, ap, diag, interpret=interpret)

    def phase3(alpha, beta, r_new, diag, p, x):
        return phase3_pallas(alpha, beta, r_new, diag, p, x,
                             interpret=interpret)

    return dot, phase2, phase3


def make_dot3(interpret: bool | None = None):
    """Fused triple-dot for the pipelined solver's single reduction."""
    if interpret is None:
        interpret = default_interpret()

    def dot3(r, u, w):
        return dot3_pallas(r, u, w, acc_dtype=r.dtype, interpret=interpret)

    return dot3
