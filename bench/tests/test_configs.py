"""What a configuration has to hold, for every configuration and cell in
``BENCHMARK.json``: a new one passes here, and every other test of the
benchmark picks it up, with new files and appended entries alone."""
import json
import pathlib

import numpy as np
import pytest
import scipy.sparse as sp

from bench import loadgen, run, systems

ROOT = pathlib.Path(__file__).resolve().parents[2]
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
CONFIGS = {c["name"]: c for c in SPEC["configs"]}
CELLS = {w["name"]: w for w in SPEC["workloads"]}
#: the keys of a configuration that ``bench/run.py``, the loops and the
#: readers use, by group
USED = {"precision": ("value_bytes", "vector_bytes"),
        "solver": ("scheme", "backend", "layout", "block_rows", "col_tile",
                   "rel_tol", "maxiter"),
        "check": ("max_true_rel_residual",)}
SEED = 2 ** 33 + 77


def config(name):
    return run.config_of({"config": name})


@pytest.fixture(scope="module", params=sorted(CONFIGS))
def small(request):
    """A configuration at its generator's ``SMALL`` size, its generator
    and its operator."""
    cfg = config(request.param)
    gen = systems.generator(cfg["generator"])
    cfg = systems.overridden(cfg, gen.SMALL)
    return cfg, gen, gen.build(cfg)


@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_configuration_file(name):
    entry = CONFIGS[name]
    path = ROOT / entry["file"]
    assert path.is_file()
    assert path == ROOT / "bench" / "configs" / f"{name}.json"
    cfg = config(name)
    assert cfg["name"] == path.stem
    assert entry["source"].strip() and cfg["source"].strip()
    for group, keys in USED.items():
        assert set(keys) <= set(cfg[group]), group
    assert sorted(cfg["reduced"]) == sorted(entry["reduced"])
    assert set(entry["reduced"]) <= set(cfg["assumed"])


def test_small_operator(small):
    cfg, gen, a = small
    assert sp.isspmatrix_csr(a) and a.dtype == np.float64
    assert a.shape[0] == a.shape[1] > 0
    assert a.has_sorted_indices
    again = gen.build(cfg)
    assert again.shape == a.shape and (again != a).nnz == 0


def test_small_right_hand_sides(small):
    cfg, gen, a = small
    b0, b1 = gen.rhs(cfg, a, SEED, 0), gen.rhs(cfg, a, SEED, 1)
    assert b0.shape == (a.shape[0],) and np.isfinite(b0).all()
    assert np.array_equal(b0, gen.rhs(cfg, a, SEED, 0))
    assert not np.array_equal(b0, b1)


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_cell_names_a_configuration_and_a_loop(cell):
    w = CELLS[cell]
    assert w["config"] in CONFIGS
    assert (ROOT / "bench" / "traffic" / f"{w['traffic']}.json").is_file()
    assert run.traffic_of(w)["loop"] in loadgen.LOOPS


@pytest.mark.parametrize("name", [m["name"] for m in SPEC["end_to_end"] +
                                  SPEC["per_layer"]])
def test_metric_workloads_name_cells(name):
    (m,) = [m for m in SPEC["end_to_end"] + SPEC["per_layer"]
            if m["name"] == name]
    assert set(m.get("workloads", [])) <= set(CELLS)
