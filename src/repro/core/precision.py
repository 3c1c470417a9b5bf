"""Mixed-precision schemes for the JPCG SpMV (paper §6, Table 1).

The paper's schemes, at the *faithful* (FP64-host) tier:

  ============  ======  ======  ======
  scheme        A       x_in    y_out
  ============  ======  ======  ======
  fp64          FP64    FP64    FP64
  mixed_v1      FP32    FP32    FP32
  mixed_v2      FP32    FP32    FP64
  mixed_v3      FP32    FP64    FP64   <- Callipepla's choice
  ============  ======  ======  ======

Main-loop vectors are *always* kept at ``vector_dtype`` (FP64 at this tier),
exactly as the paper mandates ("we always maintain the vectors in the main
loop in FP64").

TPU v5e has no native FP64 ALUs (emulation is ~2 orders of magnitude slower
than fp32), so the production tier shifts every scheme down one level:
fp64→fp32 and fp32→bf16.  The byte-ratio economics that motivate Mix-V3
(matrix value stream is half-width, vectors full-width) are identical at the
lower tier, which is the hardware-adaptation argument recorded in DESIGN.md.

  ============  ======  ======  ======
  scheme        A       x_in    y_out   (vector_dtype = fp32)
  ============  ======  ======  ======
  tpu_fp32      FP32    FP32    FP32
  tpu_v1        BF16    BF16    BF16
  tpu_v2        BF16    BF16    FP32
  tpu_v3        BF16    FP32    FP32   <- Callipepla's choice, TPU tier
  tpu_v3_df     BF16    2×FP32  2×FP32  vectors 2×FP32 (x 3×FP32)
  ============  ======  ======  ======

``tpu_v3_df`` is Mix-V3 itself on this chip: the matrix narrow, the
vectors wide.  A wide vector is a pair of fp32 words and x a triple
(:mod:`repro.core.dfloat`), flagged ``wide``; the solver under it
replaces its recurrence residual by ``b − A·x`` on the device and
reports ``CONVERGED`` only on a replaced residual
(:mod:`repro.core.batch`).  It runs on the Pallas ELLPACK path.
"""
from __future__ import annotations

import dataclasses

import jax.numpy as jnp

__all__ = ["PrecisionScheme", "get_scheme", "SCHEMES"]


@dataclasses.dataclass(frozen=True)
class PrecisionScheme:
    name: str
    matrix_dtype: jnp.dtype    # storage dtype of A's nonzero values
    spmv_in_dtype: jnp.dtype   # x as consumed by the SpMV
    spmv_acc_dtype: jnp.dtype  # multiply/accumulate dtype inside the SpMV
    vector_dtype: jnp.dtype    # main-loop vectors (r, p, x, z, ap) and scalars
    wide: bool = False         # vectors as fp32 pairs, x a triple (dfloat);
                               # vector_bytes is then one word's

    @property
    def matrix_bytes(self) -> int:
        return jnp.dtype(self.matrix_dtype).itemsize

    @property
    def vector_bytes(self) -> int:
        return jnp.dtype(self.vector_dtype).itemsize

    def nonzero_stream_bytes(self, index_bytes: int = 2) -> int:
        """Bytes per nonzero in the matrix stream (value + 1 column index).

        This mirrors the layouts actually in use: the stacked row-ELL /
        sliced-ELL slots each hold one value at ``matrix_dtype`` plus
        one *local* column index — int16 whenever the bucketed row count
        stays under 2^15 (the default here), int32 beyond.  Pass the
        real width via ``index_bytes=``
        :func:`repro.sparse.stacking.index_bytes_for`; padding overheads
        are measured, not modeled (``stream_bytes_per_nnz()`` on the
        stacked arrays).  The paper's Challenge-3 arithmetic had
        2 packed indices per nonzero (Serpens 64-bit words); our
        row-identity is the lane position, so the second index is free.
        """
        return self.matrix_bytes + index_bytes


_f64, _f32, _bf16 = jnp.float64, jnp.float32, jnp.bfloat16

SCHEMES = {
    # Faithful tier (validated on CPU with jax_enable_x64).
    "fp64":     PrecisionScheme("fp64",     _f64,  _f64,  _f64, _f64),
    "mixed_v1": PrecisionScheme("mixed_v1", _f32,  _f32,  _f32, _f64),
    "mixed_v2": PrecisionScheme("mixed_v2", _f32,  _f32,  _f64, _f64),
    "mixed_v3": PrecisionScheme("mixed_v3", _f32,  _f64,  _f64, _f64),
    # TPU-native tier (one level down; vector_dtype fp32).
    "tpu_fp32": PrecisionScheme("tpu_fp32", _f32,  _f32,  _f32, _f32),
    "tpu_v1":   PrecisionScheme("tpu_v1",   _bf16, _bf16, _bf16, _f32),
    "tpu_v2":   PrecisionScheme("tpu_v2",   _bf16, _bf16, _f32, _f32),
    "tpu_v3":   PrecisionScheme("tpu_v3",   _bf16, _f32,  _f32, _f32),
    "tpu_v3_df": PrecisionScheme("tpu_v3_df", _bf16, _f32, _f32, _f32,
                                 wide=True),
}


def get_scheme(name_or_scheme) -> PrecisionScheme:
    if isinstance(name_or_scheme, PrecisionScheme):
        return name_or_scheme
    try:
        return SCHEMES[name_or_scheme]
    except KeyError:
        raise ValueError(
            f"unknown precision scheme {name_or_scheme!r}; "
            f"available: {sorted(SCHEMES)}") from None
