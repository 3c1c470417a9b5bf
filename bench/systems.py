"""A configuration's operator and right-hand sides, by its generator's name.

Each generator is a module of its own, ``bench/generators/<name>.py``,
kept with the benchmark so that the yardstick cannot move with the
program.  It defines

* ``build(cfg)`` -- the configuration's one operator, a
  ``scipy.sparse.csr_matrix`` in float64 with sorted column indices,
  the same for every run (randomness only from the configuration's own
  ``matrix_seed``), so that every run packs the same shapes and finds
  its programs in the compile cache;
* ``rhs(cfg, a, seed, j)`` -- the run's ``j``-th right-hand side,
  drawn from the run's seed;
* ``SMALL`` -- the configuration's overrides (``{"params": {...}}``,
  merged as :func:`overridden` merges) that make the operator small
  enough for the CPU; the benchmark's tests run every cell at it.

Nothing here imports the system under test.  A new configuration with a
new operator adds its generator's file and names it in its
configuration's ``generator``; no other file changes.
"""
from __future__ import annotations

import importlib.util
import pathlib
import types

import numpy as np

GENERATORS = pathlib.Path(__file__).resolve().parent / "generators"

#: seed streams: one per kind of draw, so that a new kind of draw never
#: shifts the numbers of another
STREAM_MATRIX, STREAM_RHS, STREAM_ARRIVALS, STREAM_PICK = 1, 2, 3, 4


def rng_for(seed: int, stream: int, *more: int) -> np.random.Generator:
    """Independent generator for one kind of draw under one run seed."""
    return np.random.default_rng([int(seed), int(stream), *map(int, more)])


def generator(name: str) -> types.ModuleType:
    """The generator module ``bench/generators/<name>.py``."""
    path = GENERATORS / f"{name}.py"
    if not path.is_file():
        raise ValueError(f"no generator {name!r} in {GENERATORS}")
    spec = importlib.util.spec_from_file_location(f"bench_gen_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def overridden(cfg: dict, overrides: dict) -> dict:
    """A copy of ``cfg`` with the entries of ``overrides`` in place; an
    entry that is a dict is merged one level deep."""
    out = dict(cfg)
    for key, val in overrides.items():
        out[key] = {**out[key], **val} if isinstance(val, dict) else val
    return out


class Systems:
    """A configuration's operator and its right-hand sides."""

    def __init__(self, cfg: dict):
        self.cfg = cfg
        self.gen = generator(cfg["generator"])
        self.a = self.gen.build(cfg)

    def rhs(self, seed: int, j: int) -> np.ndarray:
        return self.gen.rhs(self.cfg, self.a, seed, j)
