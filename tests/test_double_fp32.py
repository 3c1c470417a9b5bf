"""Double-fp32 vectors (``tpu_v3_df``, :mod:`repro.core.dfloat`): the
error-free transforms against float64 numpy, the double-fp32 ELLPACK
kernel against a float64 SpMV, and the solver under the paper's
``||r|| ≤ 1e-9 ||b||`` against a plain float64 numpy Jacobi-CG.

Everything runs jitted on XLA:CPU, which fuses products into adds
shape by shape (``batch.rounded_products``); the kernel runs in the
Pallas interpreter on the same backend."""
import importlib.util
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest
import scipy.sparse as sp

from repro.core import dfloat as D
from repro.core.batch import jpcg_solve_batched
from repro.core.dfloat import DF, TF
from repro.kernels.spmv_df import spmv_pallas_df_batched, x_words_tiles
from repro.sparse.csr import CSRMatrix
from repro.sparse.ellpack import csr_to_ellpack
from repro.sparse.stacking import stack_ellpack

ROOT = pathlib.Path(__file__).resolve().parents[1]
#: what a pair of fp32 words holds: 2^-48 of the value, with room for
#: the few roundings of one operation
PAIR_REL = 1e-14
#: the paper's bound, ||r||² < 1e-12 with ||b||² ~ n: ||r|| ≤ 1e-9 ||b||
REL_TOL = 1e-9


def _generator(name):
    spec = importlib.util.spec_from_file_location(
        name, ROOT / "bench" / "generators" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def ecology2_small():
    """The benchmark's ecology2 operator at its CPU size (32 x 32)."""
    p = _generator("ecology2_grid").SMALL["params"]
    return _generator("ecology2_grid").grounded_grid(p["nx"], p["ny"],
                                                     p["ground"])


def hpcg_8():
    return _generator("hpcg_stencil27").hpcg_stencil27(8, 8, 8)


def csr(a) -> CSRMatrix:
    return CSRMatrix(a.indptr.astype(np.int64), a.indices.astype(np.int32),
                     a.data, a.shape)


def rhs(a, seed):
    """The paper's b = 1, perturbed (ecology2's right-hand side)."""
    return np.random.default_rng(seed).uniform(0.5, 1.5, a.shape[0])


def jacobi_cg(a, b, tol_rr, maxiter=20_000):
    """Plain float64 Jacobi-preconditioned CG: x0 = 0, stop at
    ``r·r ≤ tol_rr`` on the recurrence.  Returns ``(x, iterations)``."""
    d = a.diagonal()
    x = np.zeros_like(b)
    r = b.copy()
    z = r / d
    p = z.copy()
    rz, rr, k = r @ z, r @ r, 0
    while rr > tol_rr and k < maxiter:
        ap = a @ p
        alpha = rz / (p @ ap)
        x += alpha * p
        r -= alpha * ap
        rr = r @ r
        z = r / d
        rz, rz_old = r @ z, rz
        p = z + (rz / rz_old) * p
        k += 1
    return x, k


def true_rel(a, x, b):
    return np.linalg.norm(b - a @ np.asarray(x, np.float64)) / \
        np.linalg.norm(b)


def wide(v, k=2):
    return (DF if k == 2 else TF)(*(jnp.asarray(w) for w in D._words(v, k)))


def rel_err(got, want):
    return np.max(np.abs(D.to_f64(got) - want)) / np.max(np.abs(want))


@pytest.fixture(scope="module")
def vecs():
    rng = np.random.default_rng(3)
    # wide exponent range and signs, as the solver's vectors have
    a = rng.standard_normal((2, 4096)) * 10.0 ** rng.uniform(-3, 6, 4096)
    b = rng.standard_normal((2, 4096)) * 10.0 ** rng.uniform(-3, 6, 4096)
    return a, b


# --------------------------------------------- error-free transforms
def test_two_sum_is_exact(vecs):
    a, b = (v.astype(np.float32) for v in vecs)
    s, e = jax.jit(D.two_sum)(jnp.asarray(a), jnp.asarray(b))
    np.testing.assert_array_equal(
        np.asarray(s, np.float64) + np.asarray(e, np.float64),
        a.astype(np.float64) + b.astype(np.float64))
    np.testing.assert_array_equal(np.asarray(s), a + b)


def test_split_product_is_exact(vecs):
    a, b = (v.astype(np.float32) for v in vecs)
    h, l = jax.jit(D.split)(jnp.asarray(a))
    np.testing.assert_array_equal(np.asarray(h) + np.asarray(l), a)
    bits = np.asarray(h).view(np.uint32) & 0xFFF
    assert not bits.any()                       # 12 significant bits
    ph, pl = jax.jit(D.two_prod)(jnp.asarray(a), jnp.asarray(b))
    got = np.asarray(ph, np.float64) + np.asarray(pl, np.float64)
    want = a.astype(np.float64) * b.astype(np.float64)   # exact: 48 bits
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("op", ["add", "mul", "div"])
def test_pair_arithmetic(vecs, op):
    a, b = vecs
    f = {"add": lambda x, y: x + y, "mul": lambda x, y: x * y,
         "div": lambda x, y: x / y}[op]
    got = jax.jit(f)(wide(a), wide(b))
    assert rel_err(got, f(D.to_f64(wide(a)), D.to_f64(wide(b)))) < PAIR_REL


def test_pair_dot_and_axpy(vecs):
    a, b = vecs
    xa, xb = wide(a), wide(b)
    fa, fb = D.to_f64(xa), D.to_f64(xb)
    dot = jax.jit(lambda u, v: u.dot(v))(xa, xb)
    want = np.einsum("gi,gi->g", fa, fb)
    scale = np.einsum("gi,gi->g", np.abs(fa), np.abs(fb))
    assert np.all(np.abs(D.to_f64(dot) - want) < PAIR_REL * scale)
    s = wide(np.array([0.3, -7.0]))
    got = jax.jit(lambda x, s, y: x + s[:, None] * y)(xa, s, xb)
    assert rel_err(got, fa + D.to_f64(s)[:, None] * fb) < PAIR_REL


def test_triple_accumulates_to_float64(vecs):
    """x takes thousands of steps x + α p: its third word keeps every
    step's rounding, so the float64 sum of all steps comes back."""
    a, _ = vecs
    steps = a[0, :200, None] * np.linspace(1e-6, 1.0, 64)[None, :] ** 3
    x = wide(np.full(64, 1.2e6), 3)
    add = jax.jit(lambda x, s: x + s)
    for s in steps:
        x = add(x, wide(s))
    want = 1.2e6 + D.to_f64(wide(steps)).sum(axis=0)
    np.testing.assert_allclose(D.to_f64(x), want, rtol=1e-15, atol=0)


def test_kernel_matches_float64_spmv():
    """The Pallas double-fp32 ELLPACK SpMV against float64 ``A @ x``, for
    x of two and of three words, on a packed grid whose x reaches 1e6."""
    a = ecology2_small()
    st = stack_ellpack([csr_to_ellpack(csr(a), block_rows=256,
                                       col_tile=512)] * 2, bucket=True)
    x = np.stack([
        np.linspace(1.0, 1.2e6, a.shape[0]) ** 1.0,
        np.random.default_rng(5).standard_normal(a.shape[0]) * 1e3])
    for k in (2, 3):
        words = [np.asarray(w) for w in D._words(x, k)]
        xt = x_words_tiles([jnp.asarray(w) for w in words], st.n_col_tiles,
                           512)
        yh, yl = spmv_pallas_df_batched(
            jnp.asarray(st.tile_cols), jnp.asarray(st.vals, jnp.bfloat16),
            jnp.asarray(st.local_cols), xt, words=k, interpret=True)
        n = a.shape[0]
        y = (np.asarray(yh, np.float64).reshape(2, -1)[:, :n]
             + np.asarray(yl, np.float64).reshape(2, -1)[:, :n])
        xk = sum(w.astype(np.float64) for w in words)
        want = np.stack([a @ v for v in xk])
        scale = np.stack([abs(a) @ np.abs(v) for v in xk])
        assert np.all(np.abs(y - want) <= PAIR_REL * scale + 1e-300)


# ------------------------------------------------------ the solver
def solve(a, bs, scheme="tpu_v3_df", engine="vm", **kw):
    tols = [REL_TOL ** 2 * float(b @ b) for b in bs]
    return jpcg_solve_batched([csr(a)] * len(bs), bs, tol=tols,
                              maxiter=2000, scheme=scheme, backend="pallas",
                              layout="ellpack", engine=engine, **kw)


SYSTEMS = {"ecology2_small": ecology2_small, "hpcg_8": hpcg_8}


def system(name, seed):
    a = SYSTEMS[name]()
    b = rhs(a, seed) if name == "ecology2_small" else \
        a @ np.random.default_rng(seed).uniform(0.5, 1.5, a.shape[0])
    return a, b


@pytest.mark.parametrize("name", sorted(SYSTEMS))
@pytest.mark.parametrize("seed", [11, 12])
def test_reaches_the_papers_bound(name, seed):
    """``CONVERGED`` at a float64 true residual ≤ 1e-9, in at most 1.3x
    the iterations of float64 Jacobi-CG, and x as float64 Jacobi-CG's."""
    a, b = system(name, seed)
    (res,) = solve(a, [b])
    assert res.status == "CONVERGED" and res.converged
    assert true_rel(a, res.x, b) <= REL_TOL
    assert res.replacements >= 1
    x_ref, k_ref = jacobi_cg(a, b, REL_TOL ** 2 * float(b @ b))
    assert true_rel(a, x_ref, b) <= REL_TOL
    assert res.iterations <= 1.3 * k_ref
    # both residuals are at most 1e-9 ||b||, so the two solutions differ
    # by at most ||A^-1|| (2e-9 ||b||), and ||A^-1|| = 1 / λ_min(A)
    lam_min = np.linalg.eigvalsh(a.toarray())[0]
    assert np.linalg.norm(res.x - x_ref) <= 2 * REL_TOL * \
        np.linalg.norm(b) / lam_min


@pytest.mark.parametrize("seed", [11, 12, 13])
def test_float64_x_meets_the_bound_where_its_rounding_shows(seed):
    """The caller's x is the device's three words rounded to float64, and
    its check a float64 ``b − A·x``: the two roundings add ~1e-13 of
    ‖b‖ on the 32 x 32 grid (|x| ≤ 690), as they add 2–3e-10 on the
    full 1000 x 1000 grid (|x| ≤ 1.2e6).  At ``rel`` 2.5e-13 they are
    two fifths of the bound here, more than the full grid's share of
    1e-9, so a lane stopped at ``rel`` itself reads above it (2.5–2.9e-13
    on these seeds); the device stops at ``MARGIN`` of it and the
    float64 x meets it."""
    from repro.core.batch import MARGIN
    rel = 2.5e-13
    a, b = system("ecology2_small", seed)
    tol = rel ** 2 * float(b @ b)
    (res,) = jpcg_solve_batched([csr(a)], [b], tol=[tol], maxiter=2000,
                                scheme="tpu_v3_df", backend="pallas",
                                layout="ellpack")
    assert res.status == "CONVERGED"
    assert res.rr <= MARGIN ** 2 * tol
    assert true_rel(a, res.x, b) <= rel


def test_tpu_v3_fails_the_bound():
    """One precision below the configuration's: fp32 vectors stop on a
    recurrence whose true residual is orders above the bound."""
    a, b = system("ecology2_small", 11)
    (res,) = solve(a, [b], scheme="tpu_v3")
    assert true_rel(a, res.x, b) > 100 * REL_TOL


def test_vm_matches_the_phases_oracle():
    """The stream VM and the phase-fused oracle replace the same
    residuals at the same ticks: every result bit for bit."""
    a = ecology2_small()
    bs = [rhs(a, 21), rhs(a, 22)]
    vm, ph = solve(a, bs), solve(a, bs, engine="phases")
    for r, o in zip(vm, ph):
        assert (r.iterations, r.replacements, r.status) == \
            (o.iterations, o.replacements, o.status)
        assert r.replacements > 1
        np.testing.assert_array_equal(r.x, o.x)
        assert r.rr == o.rr


def test_bag_matches_single_lanes():
    """A lane's result does not depend on its bag: the replacement SpMV
    runs for every lane when any lane needs it, and is kept only where
    needed."""
    a = ecology2_small()
    bs = [rhs(a, 31), rhs(a, 32)]
    bag = solve(a, bs)
    for b, r in zip(bs, bag):
        (one,) = solve(a, [b])
        assert (one.iterations, one.replacements) == \
            (r.iterations, r.replacements)
        np.testing.assert_array_equal(one.x, r.x)


def test_counters_and_checks():
    from repro.core.metrics import solver_metrics
    a, b = system("ecology2_small", 41)
    before = solver_metrics().snapshot()
    (res,) = solve(a, [b])
    after = solver_metrics().snapshot()
    assert after["replacements"] - before.get("replacements", 0) == \
        res.replacements
    assert after["spmv_calls"] - before["spmv_calls"] == \
        1 + res.iterations + res.replacements
    for bad in (dict(backend="xla"), dict(layout="sell"),
                dict(specialize=False)):
        kw = dict(tol=1.0, scheme="tpu_v3_df", backend="pallas",
                  layout="ellpack")
        kw.update(bad)
        with pytest.raises(ValueError, match="tpu_v3_df"):
            jpcg_solve_batched([csr(a)], [b], **kw)


def test_auto_layout_is_ellpack():
    """Where the padding heuristic would pick SELL, the wide scheme
    still runs its ELLPACK kernel, and solves the matrix it holds at
    rest (values rounded to bf16) to the bound."""
    from repro.sparse import powerlaw_spd
    from repro.sparse.stacking import choose_layout
    a = powerlaw_spd(300, seed=1)
    assert choose_layout([a], default="ellpack") == "sell"
    (res,) = jpcg_solve_batched([a], tol=REL_TOL ** 2 * a.shape[0],
                                scheme="tpu_v3_df", backend="pallas")
    assert res.status == "CONVERGED"
    dense = np.zeros(a.shape)
    rows = np.repeat(np.arange(a.shape[0]), np.diff(np.asarray(a.indptr)))
    dense[rows, np.asarray(a.indices)] = np.asarray(
        jnp.asarray(np.asarray(a.data), jnp.bfloat16), np.float64)
    assert true_rel(sp.csr_matrix(dense), res.x, np.ones(a.shape[0])) \
        <= 2 * REL_TOL
