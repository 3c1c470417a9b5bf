"""``chip_smoke.py`` on CPU: it refuses to report without a chip, and its
phases and checks run end to end at a tiny size (Pallas interpreted)."""
import importlib.util
import pathlib
import shutil
import subprocess
import sys
import types

import jax
import numpy as np
import pytest

SMOKE = pathlib.Path(__file__).resolve().parents[1] / "chip_smoke.py"
_spec = importlib.util.spec_from_file_location("chip_smoke", SMOKE)
smoke = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(smoke)


def test_no_tpu_exits_nonzero_without_result(capsys):
    assert jax.devices()[0].platform == "cpu"
    assert smoke.main([]) != 0
    out, err = capsys.readouterr()
    assert '"ok"' not in out
    assert "needs a TPU" in err


def test_alone_outside_checkout_exits_nonzero(tmp_path):
    shutil.copy(SMOKE, tmp_path / SMOKE.name)
    p = subprocess.run([sys.executable, SMOKE.name], cwd=tmp_path,
                       capture_output=True, text=True, timeout=60)
    assert p.returncode != 0
    assert '"ok"' not in p.stdout
    assert "no repository checkout" in p.stderr


@pytest.mark.parametrize("status,scale", [("CONVERGED", 1.001),
                                          ("MAXITER", 1.0)])
def test_check_records_failure(status, scale):
    """A result off its bound, or not converged, fails its phase."""
    from repro.core import get_scheme
    (label, a, b), = smoke.build(smoke._poisson(6))
    r = types.SimpleNamespace(x=np.full(a.shape[0], scale), status=status,
                              iterations=1)
    failures = []
    smoke.check(label, a, b, r, get_scheme(smoke.SCHEME), failures)
    assert failures == [label]
    with pytest.raises(SystemExit) as e:
        smoke.end_phase("phase", failures)
    assert e.value.code != 0


def test_phases_tiny_interpreted(capsys):
    """Phases (a)-(c) on both backends at a tiny size: every check
    passes, the skewed bag takes SELL, and late requests are admitted."""
    skew = (("powerlaw_spd(2048)", "powerlaw_spd",
             dict(n=2048, max_deg=64, seed=0)),)
    reqs = smoke._poisson(8, 4, 7, 5, 6, 3, 9, 2, 5)
    with jax.enable_x64(False):
        smoke.one_chip(poisson_bag=smoke._poisson(12, 10, 8, 6),
                       skew_bag=skew,
                       engine_reqs={"xla": reqs, "pallas": reqs},
                       interpret=True)
    out = capsys.readouterr().out
    assert "NOT <=" not in out
    assert out.count("status=CONVERGED") == 2 * (4 + 1 + 9)
    assert "skewed bag backend=xla interpret=True layout=sell" in out
    assert "skewed bag backend=pallas interpret=True layout=sell" in out
    assert "poisson bag backend=xla interpret=True layout=rowell" in out
    assert "poisson bag backend=pallas interpret=True layout=ellpack" in out
    assert out.count("admits=9") == 2


def test_four_devices_tiny(tmp_path):
    """The ``--chips 4`` phase, requests and all, on four forced host
    devices: after the first step has compacted the pool, its lanes are
    still split over all four, and the sharded engine agrees with one
    device."""
    script = f"""
import importlib.util, jax
spec = importlib.util.spec_from_file_location("chip_smoke", {str(SMOKE)!r})
smoke = importlib.util.module_from_spec(spec)
spec.loader.exec_module(smoke)
smoke.four_chips()
"""
    env = dict(
        PATH="/usr/bin:/bin", HOME=str(tmp_path), JAX_PLATFORMS="cpu",
        PYTHONPATH=str(SMOKE.parent / "src"),
        XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, "-c", script], env=env,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode == 0, p.stderr[-4000:]
    assert "compactions=1" in p.stdout
    assert ("lane placement after the first step: 4 lanes, (device, first "
            "lane, end lane, lanes held) = [(0, 0, 1, 1), (1, 1, 2, 1), "
            "(2, 2, 3, 1), (3, 3, 4, 1)]") in p.stdout
    assert "x bit-identical for every request: True" in p.stdout
