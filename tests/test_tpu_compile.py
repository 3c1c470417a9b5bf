"""The solve path compiles for TPU v5e, at the sizes ``chip_smoke.py`` runs.

These compile for a v5e chip that is described, not attached: the TPU
compiler refuses here what the chip would refuse (block tiling, scalar
stores to VMEM, unsupported gathers, VMEM overflow), at no chip time.
Nothing runs, so nothing here says anything about results or speed.

The topology is described only inside the module fixture, never while a
module is imported: only one process at a time may load the TPU
library, and under several test workers only the worker given this
file may do so.  Keep every such compile in this one file.
"""
import importlib.util
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.compile import canonical_program
from repro.core.precision import get_scheme
from repro.core.vm import make_vm_runner
from repro.kernels.dot import dot3_pallas, dot_pallas
from repro.kernels.fused_phase import phase2_pallas, phase3_pallas
from repro.kernels.spmv import spmv_pallas_batched, spmv_pallas_sell
from repro.sparse.stacking import bucket_up, fine_bucket_up

_spec = importlib.util.spec_from_file_location(
    "chip_smoke", pathlib.Path(__file__).resolve().parents[1] / "chip_smoke.py")
smoke = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(smoke)

SCHEME = get_scheme(smoke.SCHEME)
#: the Poisson bag's bucket: 4 lanes padded to poisson_3d(128)'s 2^21 rows
G = len(smoke.POISSON_BAG)
N_PAD = bucket_up(max(kw["n_side"] ** 3 for _, _, kw in smoke.POISSON_BAG))
#: row-ELL width: 7 nnz/row bucketed
ROWELL_W = 8
#: ELLPACK slabs and slots: the buckets of the bag's systems (4 slabs
#: of up to 5 slots at 256 rows x 512 columns)
ELL_T, ELL_E = 4, 8


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def shape(topo):
    """``shape(dims, dtype)`` -> a described-device argument; the
    persistent compilation cache is off meanwhile (a described-device
    compile is written to it but cannot be read back without a chip)."""
    from jax.experimental.compilation_cache import compilation_cache
    one_chip = SingleDeviceSharding(topo.devices[0])
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield lambda dims, dt: jax.ShapeDtypeStruct(dims, dt, sharding=one_chip)
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(fn, *args):
    # the chip path runs with x64 off (the suite turns it on globally for
    # the fp64 tier; Mosaic takes no 64-bit types)
    with jax.enable_x64(False):
        return fn.lower(*args).compile()


def _kernels(compiled) -> int:
    return compiled.as_text().count("tpu_custom_call")


def test_spmv_ellpack_batched(shape):
    B = N_PAD // smoke.BLOCK_ROWS
    blk = (G, B, ELL_T, ELL_E, smoke.BLOCK_ROWS)
    c = _compile(
        jax.jit(lambda tc, v, lc, x: spmv_pallas_batched(
            tc, v, lc, x, scheme=SCHEME)),
        shape((G, B, ELL_T), jnp.int32),
        shape(blk, SCHEME.matrix_dtype), shape(blk, jnp.int32),
        shape((G, N_PAD // smoke.COL_TILE, smoke.COL_TILE), jnp.float32))
    assert _kernels(c) >= G          # one launch per system


def test_spmv_ellpack_batched_eighth_octave(shape):
    """The grid off the power-of-two edges: HPCG 104³'s two lanes as
    stacked, 4394 row blocks -> 4608 (9·2^9), 6 slabs, 9 slots -> 16,
    2197 x tiles -> 2304."""
    n = 104 ** 3
    B = fine_bucket_up(-(-n // smoke.BLOCK_ROWS))
    n_tiles = fine_bucket_up(-(-n // smoke.COL_TILE))
    assert (B, n_tiles) == (9 << 9, 9 << 8)
    g, T, E = 2, 6, 16
    blk = (g, B, T, E, smoke.BLOCK_ROWS)
    c = _compile(
        jax.jit(lambda tc, v, lc, x: spmv_pallas_batched(
            tc, v, lc, x, scheme=SCHEME)),
        shape((g, B, T), jnp.int32),
        shape(blk, SCHEME.matrix_dtype), shape(blk, jnp.int32),
        shape((g, n_tiles, smoke.COL_TILE), jnp.float32))
    assert _kernels(c) >= g          # one launch per system


def test_spmv_sell(shape):
    from repro.sparse.stacking import stack_sell
    st = stack_sell([a for _, a, _ in smoke.build(smoke.SKEW_BAG)],
                    scheme=SCHEME)
    g, L = st.vals.shape
    c = _compile(
        jax.jit(lambda cols, vals, x: spmv_pallas_sell(
            cols, vals, x, groups=st.groups, scheme=SCHEME)),
        shape((g, L), st.cols.dtype), shape((g, L), SCHEME.matrix_dtype),
        shape((g, st.padded_rows), jnp.float32))
    assert _kernels(c) == sum(w > 0 for _, w in st.groups)


@pytest.mark.parametrize("name", ["dot", "dot3", "phase2", "phase3"])
def test_vector_kernels(shape, name):
    v = shape((N_PAD,), jnp.float32)
    s = shape((), jnp.float32)
    fn, args = {
        "dot": (lambda a, b: dot_pallas(a, b), (v, v)),
        "dot3": (lambda a, b, c: dot3_pallas(a, b, c), (v, v, v)),
        "phase2": (lambda al, r, ap, d: phase2_pallas(al, r, ap, d),
                   (s, v, v, v)),
        "phase3": (lambda al, be, r, d, p, x: phase3_pallas(
            al, be, r, d, p, x), (s, s, v, v, v, v)),
    }[name]
    assert _kernels(_compile(jax.jit(fn), *args)) == 1


def test_vm_runner_xla(shape):
    """The specialized VM runner on the XLA row-ELL backend: no kernel,
    and the whole solve fits one 16 GB chip."""
    run = make_vm_runner(
        backend="xla", scheme=SCHEME, maxiter=smoke.MAXITER,
        with_trace=False, layout="rowell", interpret=False,
        program=np.asarray(canonical_program("paper"), np.int32))
    vec = shape((G, N_PAD), jnp.float32)
    c = _compile(run, (shape((G, ROWELL_W, N_PAD), jnp.int32),
                       shape((G, ROWELL_W, N_PAD), SCHEME.matrix_dtype)),
                 vec, vec, vec, shape((G,), jnp.float32))
    assert _kernels(c) == 0
    mem = c.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 16e9


#: ecology2's operand (1000 x 1000 grounded grid, 999,999 rows) as two
#: lanes stack it: 3907 row blocks -> 4096, 5 slabs, 3 slots -> 4, 1954
#: x tiles -> 2048
ECO_G, ECO_B, ECO_T, ECO_E, ECO_TILES = 2, 4096, 5, 4, 2048


@pytest.mark.parametrize("words", [2, 3])
def test_spmv_ellpack_df(shape, words):
    """The double-fp32 kernel at ecology2's stacked dims, with x as a
    pair (a search direction) and as a triple (the solution)."""
    from repro.kernels.spmv_df import spmv_pallas_df_batched
    blk = (ECO_G, ECO_B, ECO_T, ECO_E, smoke.BLOCK_ROWS)
    nc = smoke.COL_TILE // 128
    c = _compile(
        jax.jit(lambda tc, v, lc, x: spmv_pallas_df_batched(
            tc, v, lc, x, words=words)),
        shape((ECO_G, ECO_B, ECO_T), jnp.int32),
        shape(blk, jnp.bfloat16), shape(blk, jnp.int32),
        shape((ECO_G, ECO_TILES, words * nc, 128), jnp.float32))
    assert _kernels(c) == ECO_G      # one launch per system


def test_vm_runner_df(shape):
    """The specialized VM runner under ``tpu_v3_df`` at ecology2's dims:
    a launch per lane for the recurrence's SpMV, the warm-up's and the
    replacement's, and the whole solve fits one 16 GB chip."""
    from repro.core.dfloat import DF, TF
    run = make_vm_runner(
        backend="pallas", scheme="tpu_v3_df", maxiter=20_000,
        with_trace=False, layout="ellpack", block_rows=smoke.BLOCK_ROWS,
        col_tile=smoke.COL_TILE, n_col_tiles=ECO_TILES, interpret=False,
        program=np.asarray(canonical_program("paper"), np.int32))
    n = ECO_B * smoke.BLOCK_ROWS
    blk = (ECO_G, ECO_B, ECO_T, ECO_E, smoke.BLOCK_ROWS)
    w = shape((ECO_G, n), jnp.float32)
    c = _compile(run, (shape((ECO_G, ECO_B, ECO_T), jnp.int32),
                       shape(blk, jnp.bfloat16), shape(blk, jnp.int32)),
                 DF(w, w), DF(w, w), TF(w, w, w), shape((ECO_G,), jnp.float32))
    assert _kernels(c) == 3 * ECO_G
    mem = c.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 16e9
