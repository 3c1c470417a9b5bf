"""``bench/run.py`` end to end on the CPU at small sizes: it refuses to
run without a TPU or outside a checkout; every cell's run is correct;
the control (the program's ``tpu_v1`` path) and each planted fault of
the timed path come out not correct."""
import dataclasses
import json
import os
import pathlib
import shutil
import subprocess
import sys
import time

import numpy as np
import pytest

from bench import run, systems

ROOT = pathlib.Path(__file__).resolve().parents[2]
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in SPEC["workloads"]]
LOOP = {w["name"]: run.traffic_of(w)["loop"] for w in SPEC["workloads"]}
STREAM = next(c for c in CELLS if LOOP[c] == "open")
SECONDS = 1.5


def small(cell, **solver):
    """Overrides that run ``cell`` at its generator's ``SMALL`` size."""
    gen = run.config_of(run.cell_of(SPEC, cell))["generator"]
    cfg = dict(systems.generator(gen).SMALL)
    if solver:
        cfg["solver"] = solver
    return {"cfg": cfg, "traffic": {"drain_s": 3.0}}


def run_small(cell, seed=2 ** 33 + 1, **solver):
    return run.run_cell(cell, seed, SECONDS, False, require_tpu=False,
                        overrides=small(cell, **solver),
                        t_start=time.perf_counter())


def cli(cwd, *extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", CELLS[0], "--seed",
         str(2 ** 32 + 3), "--seconds", "1", "--trace", "0", *extra],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_exits_nonzero_without_a_tpu():
    p = cli(ROOT)
    assert p.returncode != 0 and p.stdout.strip() == ""
    assert "needs a TPU" in p.stderr


def test_exits_nonzero_outside_a_checkout(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = cli(tmp_path)
    assert p.returncode != 0 and p.stdout.strip() == ""


@pytest.mark.parametrize("cell", CELLS)
def test_cell_runs_correct(cell, capsys):
    out = run_small(cell)
    assert out["correct"], out["check"]
    assert out["failed"] == 0 and out["attempted"] > 0
    assert out["window_compiles"] == 0
    names = {m["name"] for m in run.metrics_of(SPEC, cell, "end_to_end")}
    assert set(out["metrics"]) == names and "setup_s" in names
    assert list(out)[-1] == "check"
    err = capsys.readouterr().err.strip().splitlines()
    assert err[-2].startswith("check residual")
    assert err[-1].startswith("check unconverged")


@pytest.mark.parametrize("cell", CELLS)
def test_control_is_not_correct(cell):
    out = run_small(cell, scheme="tpu_v1")
    assert not out["correct"]
    assert out["check"]["residual"]["value"] > \
        out["check"]["residual"]["limit"]


def altered(results):
    r = results[0]
    x = np.array(r.x, np.float32)
    x[x.size // 2] += 1.0
    results[0] = dataclasses.replace(r, x=x)
    return results


def half_dropped(results):
    """The first half of the lanes solved, the rest handed back as x0."""
    keep = len(results) // 2
    return results[:keep] + [dataclasses.replace(
        r, x=np.zeros(np.shape(r.x), np.float32))
        for r in results[keep:]]


def unchanged(results):
    return [dataclasses.replace(r, x=np.zeros(np.shape(r.x), np.float32))
            for r in results]


@pytest.mark.parametrize("cell", [c for c in CELLS if LOOP[c] == "closed"])
@pytest.mark.parametrize("fault", ["altered", "half", "unchanged"])
def test_bag_faults_are_caught(cell, fault, monkeypatch):
    import repro.core as core
    real = core.jpcg_solve_batched

    def broken(problems, bs, **kw):
        res = list(real(problems, bs, **kw))
        if fault == "altered":
            return altered(res)
        if fault == "half":
            return half_dropped(res)
        return unchanged(res)

    monkeypatch.setattr(core, "jpcg_solve_batched", broken)
    out = run_small(cell)
    assert not out["correct"] and out["failed"] > 0


@pytest.mark.parametrize("fault", ["altered", "half", "unchanged"])
def test_stream_faults_are_caught(fault, monkeypatch):
    """Faults planted in the engine once the window opens (set-up's
    warm-up runs the engine as it is)."""
    from bench import loadgen
    from repro.serve import solver_engine as se
    real_harvest, real_step = se._Pool.harvest, se._Pool.step
    real_window = loadgen.OpenLoop.window
    on = []

    def window(self, *args, **kw):
        on.append(True)
        return real_window(self, *args, **kw)

    def harvest(self):
        done = real_harvest(self)
        if on and fault == "altered" and done:
            rid = min(done)
            done[rid] = altered([done[rid]])[0]
        if on and fault == "half":
            done = {r: v for r, v in done.items() if r % 2 == 0}
        return done

    def step(self):
        if not (on and fault == "unchanged"):
            real_step(self)

    monkeypatch.setattr(loadgen.OpenLoop, "window", window)
    monkeypatch.setattr(se._Pool, "harvest", harvest)
    monkeypatch.setattr(se._Pool, "step", step)
    out = run_small(STREAM)
    assert not out["correct"] and out["failed"] > 0
