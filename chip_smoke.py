#!/usr/bin/env python3
"""Smoke run of the CG solver service on a TPU, through its public entry points.

    python chip_smoke.py             # one chip: phases (a)-(c)
    python chip_smoke.py --chips 4   # four chips: the lane-sharded engine
                                     # against the same requests on one chip

Phases, in one process, with x64 off and the ``tpu_v3`` scheme (bf16
matrix at rest, fp32 vectors; v5e has no fp64 units):

(a) ``jpcg_solve_batched`` on a Poisson bag led by ``poisson_3d(128)``
    (2,097,152 rows, 7 nnz/row, coefficients exact in bf16) and on a
    skewed power-law bag (n = 2^16, where the auto layout picks SELL),
    once with ``backend="xla"`` and once with ``backend="pallas"``
    compiled (``interpret=False``);
(b) a ``SolverEngine`` on each backend takes nine requests of mixed
    sizes, the last three after its first ``step()``, and steps until
    drained;
(c) every result must be ``CONVERGED`` with its true relative residual
    ‖b − A·x‖/‖b‖, computed on the host in float64 with scipy.sparse,
    within its bound (:func:`residual_bound`).

Every right-hand side is HPCG's: ``b = A·1``, so the exact solution is
all ones.  Any failed check exits non-zero at the end of its phase.
Without a TPU, or outside a checkout of the repository, it exits
non-zero before printing a result.  The times printed are smoke timings
(compile seconds and the rest of one call's wall clock), not benchmark
numbers.  The last line is one JSON object:
``{"ok": true, "device": {"platform", "kind", "count"}}``.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

import numpy as np

REPO = pathlib.Path(__file__).resolve().parent
SCHEME = "tpu_v3"
MAXITER = 5000
#: the solver stops at ‖r‖ ≤ SOLVE_REL·‖b‖ on its recurrence residual
SOLVE_REL = 1e-5
#: bound on the true relative residual where A is exact in the scheme's
#: matrix dtype; the margin over SOLVE_REL covers the recurrence drifting
#: from the true residual in fp32
TRUE_REL = 1e-4
#: ELLPACK tiling of the Pallas backend (block_rows, col_tile)
BLOCK_ROWS, COL_TILE = 256, 512
ENGINE_SLOTS = 8
ENGINE_CHUNK = 64
#: engine requests submitted after the first step()
ENGINE_LATE = 3


def _poisson(*sides):
    return tuple((f"poisson_3d({s})", "poisson_3d", dict(n_side=s))
                 for s in sides)


#: (label, generator name, kwargs) — built with ``repro.sparse``.
#: Poisson sides are ones whose ELLPACK rows span at most 4 column
#: tiles (256 rows x 512 columns), so the Pallas bucket is 4 slabs, not 8.
POISSON_BAG = _poisson(128, 112, 96, 80)
SKEW_BAG = (("powerlaw_spd(65536,s0)", "powerlaw_spd",
             dict(n=1 << 16, max_deg=1024, seed=0)),
            ("powerlaw_spd(65536,s1)", "powerlaw_spd",
             dict(n=1 << 16, alpha=2.0, max_deg=1024, seed=1)))
#: engine requests per backend, in submission order.  XLA's row-ELL
#: gather moves about 8e7 elements/s on a v5e (0.84 s per matvec of the
#: Poisson bag), so an 8-lane pool padded to 2^21 rows would take about
#: 1.7 s per iteration there: its requests stop at 48^3 rows.
ENGINE_REQS = {"pallas": _poisson(128, 24, 112, 32, 96, 48, 64, 80, 16),
               "xla": _poisson(48, 20, 40, 24, 36, 28, 16, 32, 12)}


class SmokeFailure(SystemExit):
    """A failed check: exits non-zero with the message on stderr."""


def say(*parts) -> None:
    print(*parts, flush=True)


def build(specs):
    """``[(label, csr, b)]`` with HPCG's right-hand side ``b = A·1``."""
    from repro import sparse
    out = []
    for label, fn, kw in specs:
        a = getattr(sparse, fn)(**kw)
        out.append((label, a, _scipy(a) @ np.ones(a.shape[0])))
    return out


def _scipy(a):
    import scipy.sparse as sp
    return sp.csr_matrix((a.data, a.indices, a.indptr), shape=a.shape)


def _tol(b):
    """The solver's absolute ‖r‖² target for right-hand side ``b``."""
    return SOLVE_REL ** 2 * float(b @ b)


def residual_bound(a, x, b, scheme):
    """Bound on the true relative residual of ``x`` for ``A x = b``.

    ``TRUE_REL``, plus, where A is not exact in the scheme's matrix
    dtype, its rounding at rest, ``eps(matrix)·‖|A|·|x|‖/‖b‖``.
    Returns (bound, A exact)."""
    import jax.numpy as jnp
    md = np.dtype(scheme.matrix_dtype)
    if np.array_equal(a.data.astype(md).astype(np.float64), a.data):
        return TRUE_REL, True
    scale = np.linalg.norm(abs(_scipy(a)) @ abs(x)) / np.linalg.norm(b)
    return TRUE_REL + float(jnp.finfo(md).eps) * float(scale), False


def check(label, a, b, r, scheme, failures) -> None:
    """Host float64 check of one result; prints its line and records a
    failure instead of stopping the phase early."""
    x = np.asarray(r.x, np.float64)
    rel = float(np.linalg.norm(b - _scipy(a) @ x) / np.linalg.norm(b))
    err = float(np.linalg.norm(x - 1.0) / np.sqrt(x.size))
    bound, exact = residual_bound(a, x, b, scheme)
    ok = r.status == "CONVERGED" and np.isfinite(rel) and rel <= bound
    say(f"(c)   {label} n={a.shape[0]} iterations={r.iterations} "
        f"status={r.status} residual {rel:.3e} "
        f"{'<=' if ok else 'NOT <='} bound {bound:.3e}"
        f"{'' if exact else ' (A rounded to bf16)'}; "
        f"rms error against x = 1: {err:.3e}")
    if not ok:
        failures.append(label)


def end_phase(name, failures) -> None:
    if failures:
        raise SmokeFailure(f"FAIL {name}: {failures}")


class CompileClock:
    """Wall seconds while open, and the part spent in backend compiles
    (JAX's own compile-duration events; a persistent-cache hit counts
    none)."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __enter__(self):
        import jax
        self.seconds = 0.0
        self.t0 = time.perf_counter()
        jax.monitoring.register_event_duration_secs_listener(self._on)
        return self

    def _on(self, event, duration, **_):
        if event == self.EVENT:
            self.seconds += duration

    def __exit__(self, *exc):
        import jax
        self.wall = time.perf_counter() - self.t0
        jax.monitoring.unregister_event_duration_listener(self._on)

    def __str__(self):
        return (f"smoke timings (not benchmark numbers): compile "
                f"{self.seconds:.2f} s, solve {self.wall - self.seconds:.2f} s")


def solve_bag(name, bag, *, backend, interpret):
    """Phase (a): one batched solve of ``bag`` on ``backend``."""
    import jax
    from repro.core import get_scheme, jpcg_solve_batched
    from repro.sparse.stacking import choose_layout
    scheme = get_scheme(SCHEME)
    csrs = [a for _, a, _ in bag]
    layout = choose_layout(csrs, default="rowell" if backend == "xla"
                           else "ellpack")
    say(f"(a) {name} backend={backend} interpret={interpret} "
        f"layout={layout} n={[a.shape[0] for a in csrs]}")
    with CompileClock() as clock:
        res = jpcg_solve_batched(
            csrs, [b for _, _, b in bag], tol=[_tol(b) for _, _, b in bag],
            maxiter=MAXITER, scheme=SCHEME, backend=backend,
            block_rows=BLOCK_ROWS, col_tile=COL_TILE, interpret=interpret)
        jax.block_until_ready([r.x for r in res])
    say(f"(a) {name} backend={backend} {clock}")
    failures = []
    for (label, a, b), r in zip(bag, res):
        check(label, a, b, r, scheme, failures)
    end_phase(f"{name}/{backend}", failures)


def run_engine(reqs, *, backend, interpret, mesh=None, on_first_step=None):
    """Phase (b): the engine takes ``reqs``, the last ``ENGINE_LATE`` after
    its first step, and steps until drained.  Returns ``{label: result}``."""
    from repro.serve import SolverEngine, SolverEngineConfig
    eng = SolverEngine(SolverEngineConfig(
        batch_slots=ENGINE_SLOTS, scheme=SCHEME, maxiter=MAXITER,
        chunk_iters=ENGINE_CHUNK, backend=backend, block_rows=BLOCK_ROWS,
        col_tile=COL_TILE, interpret=interpret, mesh=mesh))
    label_of = {}

    def submit(label, a, b):
        label_of[eng.submit(a, b, tol=_tol(b))] = label

    with CompileClock() as clock:
        for req in reqs[:-ENGINE_LATE]:
            submit(*req)
        eng.step()
        steps = 1
        if on_first_step is not None:
            on_first_step(eng)
        late = list(reqs[-ENGINE_LATE:])
        while late or eng.active_count:
            while late and eng.free_slots() > 0:
                submit(*late.pop(0))
            eng.step()
            steps += 1
    if sorted(eng.results) != sorted(label_of):
        raise SmokeFailure(f"FAIL engine/{backend}: harvested "
                           f"{sorted(eng.results)} of {sorted(label_of)}")
    m = eng.metrics()
    layouts = sorted({p.layout for p in eng._pools.values()})
    say(f"(b) engine backend={backend} interpret={interpret} "
        f"layout={','.join(layouts)} mesh="
        f"{None if mesh is None else dict(mesh.shape)} "
        f"n={[a.shape[0] for _, a, _ in reqs]} steps={steps} "
        f"iterations={m.get('iterations', 0)} admits={m.get('admits', 0)} "
        f"growths={m.get('growths', 0)} "
        f"compactions={m.get('compactions', 0)} {clock}")
    return {label_of[rid]: r for rid, r in eng.results.items()}


def check_engine(reqs, results, name):
    from repro.core import get_scheme
    scheme = get_scheme(SCHEME)
    failures = []
    for label, a, b in reqs:
        check(label, a, b, results[label], scheme, failures)
    end_phase(f"engine/{name}", failures)


def one_chip(*, poisson_bag=POISSON_BAG, skew_bag=SKEW_BAG,
             engine_reqs=ENGINE_REQS, interpret=False):
    poisson, skew = build(poisson_bag), build(skew_bag)
    for backend in ("xla", "pallas"):
        solve_bag("poisson bag", poisson, backend=backend,
                  interpret=interpret)
        solve_bag("skewed bag", skew, backend=backend, interpret=interpret)
    del poisson, skew
    for backend in ("xla", "pallas"):
        reqs = build(engine_reqs[backend])
        results = run_engine(reqs, backend=backend, interpret=interpret)
        check_engine(reqs, results, backend)


def four_chips(engine_reqs=ENGINE_REQS["xla"]):
    """The XLA engine's requests with the lane axis over every chip, then
    on chip 0 alone; status, iterations and x must agree."""
    import jax
    from repro.core.shard import lane_mesh
    devices = jax.devices()
    if len(devices) != 4:
        raise SmokeFailure(f"FAIL --chips 4 sees {len(devices)} devices")
    reqs = build(engine_reqs)
    placement = {}

    def record(eng):
        for pool in eng._pools.values():
            shards = pool.state.mem.addressable_shards
            placement["lanes"] = pool.state.mem.shape[1]
            placement["shards"] = sorted(
                (s.device.id, *s.index[1].indices(placement["lanes"])[:2],
                 s.data.shape[1]) for s in shards)

    sharded = run_engine(reqs, backend="xla", interpret=False,
                         mesh=lane_mesh(), on_first_step=record)
    lanes = placement["lanes"]
    per = lanes // len(devices)
    say(f"(b) lane placement after the first step: {lanes} lanes, "
        f"(device, first lane, end lane, lanes held) = "
        f"{placement['shards']}")
    held = {d for d, _, _, k in placement["shards"] if k == per}
    starts = sorted(a for _, a, _, _ in placement["shards"])
    if (held != {d.id for d in devices}
            or starts != list(range(0, lanes, per))):
        raise SmokeFailure("FAIL lanes are not split evenly over the "
                           f"four chips: {placement['shards']}")
    with jax.default_device(devices[0]):
        single = run_engine(reqs, backend="xla", interpret=False)
    check_engine(reqs, sharded, "xla sharded")
    identical = True
    for label, _, _ in reqs:
        s, o = sharded[label], single[label]
        xs, xo = np.asarray(s.x, np.float64), np.asarray(o.x, np.float64)
        if s.status != o.status or s.iterations != o.iterations:
            raise SmokeFailure(
                f"FAIL {label}: sharded {s.status}/{s.iterations} vs "
                f"one chip {o.status}/{o.iterations}")
        same = np.array_equal(xs, xo)
        identical &= same
        rel = float(np.linalg.norm(xs - xo) / np.linalg.norm(xo))
        if not same and rel > 1e-6:
            raise SmokeFailure(f"FAIL {label}: sharded x differs from one "
                               f"chip by {rel:.3e} relative")
        say(f"(c)   {label}: sharded vs one chip: status {s.status}, "
            f"iterations {s.iterations}, x bit-identical={same} "
            f"(relative difference {rel:.3e})")
    say(f"(c) four chips vs one chip: x bit-identical for every request: "
        f"{identical}")


def main(argv=None) -> int:
    t0 = time.perf_counter()
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the lane-sharded engine phase over "
                         "four chips, against the same requests on one chip")
    args = ap.parse_args(argv)
    if not (REPO / "src" / "repro").is_dir():
        print(f"chip_smoke: no repository checkout around {REPO}",
              file=sys.stderr)
        return 2
    sys.path.insert(0, str(REPO / "src"))
    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found {dev.platform!r}",
              file=sys.stderr)
        return 2
    jax.config.update("jax_enable_x64", False)
    from repro.compile_cache import enable_compile_cache
    say(f"device_kind={dev.device_kind} platform={dev.platform} "
        f"devices={len(jax.devices())} jax={jax.__version__} "
        f"compilation_cache={enable_compile_cache()}")
    if args.chips == 4:
        four_chips()
    else:
        one_chip()
    stats = dev.memory_stats() or {}
    if "peak_bytes_in_use" in stats:
        say(f"peak device memory on {dev.id}: "
            f"{stats['peak_bytes_in_use'] / 2**30:.2f} GiB")
    say(f"smoke total: {time.perf_counter() - t0:.1f} s wall")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
