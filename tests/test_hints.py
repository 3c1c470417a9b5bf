"""Activation-hint machinery: no-op without a mesh, axis resolution."""
import jax
import jax.numpy as jnp
import numpy as np

from repro.distributed import hints
from repro.launch.mesh import make_mesh


def test_noop_without_context():
    x = jnp.ones((4, 8))
    y = hints.hint(x, hints.DATA, hints.MODEL)
    assert y is x                      # literally untouched


def test_resolution_single_device():
    mesh = make_mesh((1, 1), ("data", "model"))
    with hints.sharding_hints(mesh):
        assert hints.active_mesh() is mesh
        x = jnp.arange(8.0).reshape(2, 4)
        y = hints.hint(x, hints.DATA, hints.MODEL)
        np.testing.assert_array_equal(np.asarray(y), np.asarray(x))
    assert hints.active_mesh() is None


def test_missing_axes_dropped():
    mesh = make_mesh((1,), ("rows",))   # no data/model axes
    with hints.sharding_hints(mesh):
        x = jnp.ones((4, 4))
        y = hints.hint(x, hints.DATA, hints.MODEL)
        assert y is x                  # all entries resolved to None


def test_context_nesting_restores():
    mesh = make_mesh((1,), ("rows",))
    with hints.sharding_hints(mesh):
        with hints.sharding_hints(None):
            assert hints.active_mesh() is None
        assert hints.active_mesh() is mesh


def test_hint_inside_jit_traces():
    mesh = make_mesh((1, 1), ("data", "model"))

    def f(x):
        return hints.hint(x, hints.DATA, None) * 2.0

    with hints.sharding_hints(mesh):
        y = jax.jit(f)(jnp.ones((2, 2)))
    np.testing.assert_array_equal(np.asarray(y), 2.0)
