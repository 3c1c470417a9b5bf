"""Public JPCG solver API.

>>> from repro.core.cg import jpcg_solve
>>> res = jpcg_solve(A, b, scheme="mixed_v3", tol=1e-12, maxiter=20_000)
>>> res.x, res.iterations, res.converged

Matches the paper's evaluation protocol (§7.1): b defaults to all-ones,
x0 to all-zeros, stop criterion ‖r‖² < 1e-12, 20 K max iterations.

``A`` may be a :class:`~repro.sparse.csr.CSRMatrix`, a
:class:`~repro.sparse.bell.BellMatrix`, a dense array, or a matrix-free
callable (with explicit ``diag``/``n``) — the "arbitrary problem" goal of
the paper's Challenge 1: the compiled program is reused across problems of
the same padded bucket, and termination is decided on the fly inside the
``lax.while_loop``.

``method``:
  * ``"vsr"``       — the paper-faithful three-phase loop (default);
  * ``"pipelined"`` — beyond-paper single-reduction variant (see
    :mod:`repro.core.pipelined`).

``backend``:
  * ``"xla"``    — pure-jnp phase ops (runs everywhere; default);
  * ``"pallas"`` — Pallas kernels for SpMV + fused phases (TPU layout;
    ``interpret=True`` on CPU).

For solving MANY independent systems per compiled call (the serving
path), see :func:`repro.core.batch.jpcg_solve_batched` — also reachable
as ``repro.core.cg.jpcg_solve_batched``.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import phases as _phases
from repro.core import pipelined as _pipe
from repro.core.operators import as_operator
from repro.core.precision import get_scheme

__all__ = ["CGResult", "jpcg_solve", "jpcg_solve_batched"]


def __getattr__(name):
    # Lazy: batch.py imports CGResult from here, so the batched entry
    # point is resolved on first touch to avoid an import cycle.
    if name == "jpcg_solve_batched":
        from repro.core.batch import jpcg_solve_batched
        return jpcg_solve_batched
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")


@dataclasses.dataclass
class CGResult:
    x: jax.Array
    iterations: int
    rr: float               # final ‖r‖²
    converged: bool
    residual_trace: Optional[np.ndarray]   # rr per iteration, if requested
    scheme: str
    method: str
    # Exit status name (repro.core.metrics.STATUS_NAMES): "CONVERGED" /
    # "MAXITER" / "BREAKDOWN_INDEFINITE" / "BREAKDOWN_NONFINITE"; None
    # from paths that predate the health layer or with_status=False.
    status: Optional[str] = None
    # True when the serving engine's escalation policy re-ran this
    # request at fp64 after a mixed-precision breakdown.
    retried: bool = False
    # Residual replacements on the device (wide schemes, tpu_v3_df).
    replacements: int = 0

    def __repr__(self) -> str:  # keep array printing out of logs
        extra = f", status={self.status}" if self.status else ""
        extra += ", retried" if self.retried else ""
        return (f"CGResult(iters={self.iterations}, rr={self.rr:.3e}, "
                f"converged={self.converged}, scheme={self.scheme}, "
                f"method={self.method}{extra})")


@partial(jax.jit, static_argnames=("tol", "maxiter", "scheme", "with_trace",
                                   "backend"))
def _run_vsr(op, diag, b, x0, *, tol, maxiter, scheme, with_trace,
             backend="xla"):
    st = _phases.init_state(op.matvec, diag, b, x0, maxiter=maxiter,
                            scheme=scheme, with_trace=with_trace)
    phase_ops = None
    if backend == "pallas":
        from repro.kernels.ops import make_phase_ops
        phase_ops = make_phase_ops()
    return _phases.jpcg_loop(op.matvec, diag, st, tol=tol, maxiter=maxiter,
                             scheme=scheme, phase_ops=phase_ops)


@partial(jax.jit, static_argnames=("tol", "maxiter", "scheme", "with_trace",
                                   "replace_every"))
def _run_pipe(op, diag, b, x0, *, tol, maxiter, scheme, with_trace,
              replace_every):
    st = _pipe.pipecg_init(op.matvec, diag, b, x0, maxiter=maxiter,
                           scheme=scheme, with_trace=with_trace)
    return _pipe.pipecg_loop(op.matvec, diag, b, st, tol=tol, maxiter=maxiter,
                             scheme=scheme, replace_every=replace_every)


def jpcg_solve(a, b=None, x0=None, *, tol: float = 1e-12,
               maxiter: int = 20_000, scheme="mixed_v3", method: str = "vsr",
               backend: str = "xla", diag=None, n: Optional[int] = None,
               with_trace: bool = False, replace_every: int = 50,
               block_rows: int = 256, col_tile: int = 512) -> CGResult:
    scheme = get_scheme(scheme)
    if scheme.vector_dtype == jnp.float64 and not jax.config.read("jax_enable_x64"):
        raise RuntimeError(
            f"scheme {scheme.name!r} needs fp64 vectors: enable x64 via "
            "jax.config.update('jax_enable_x64', True) before creating arrays, "
            "or use a TPU-tier scheme (tpu_v3, ...).")

    if backend == "pallas":
        from repro.kernels.ops import bell_operator_pallas
        op = bell_operator_pallas(a, scheme, diag=diag,
                                  block_rows=block_rows, col_tile=col_tile)
    elif backend == "xla":
        op = as_operator(a, scheme, diag=diag, n=n, block_rows=block_rows,
                         col_tile=col_tile)
    else:
        raise ValueError(f"unknown backend {backend!r}")

    vd = scheme.vector_dtype
    n_ = op.n
    b = (jnp.ones(n_, vd) if b is None else jnp.asarray(b)).astype(vd)
    x0 = (jnp.zeros(n_, vd) if x0 is None else jnp.asarray(x0)).astype(vd)
    d = jnp.asarray(op.diag).astype(vd)

    if method == "vsr":
        st = _run_vsr(op, d, b, x0, tol=tol, maxiter=maxiter,
                      scheme=scheme, with_trace=with_trace, backend=backend)
    elif method == "pipelined":
        st = _run_pipe(op, d, b, x0, tol=tol, maxiter=maxiter,
                       scheme=scheme, with_trace=with_trace,
                       replace_every=replace_every)
    else:
        raise ValueError(f"unknown method {method!r}")

    iters = int(st.i)
    rr = float(st.rr)
    trace = None
    if with_trace:
        trace = np.asarray(st.trace)[:iters]
    return CGResult(x=st.x, iterations=iters, rr=rr,
                    converged=bool(rr <= tol), residual_trace=trace,
                    scheme=scheme.name, method=method)
