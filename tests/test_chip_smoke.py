"""chip_smoke.py at tiny sizes on the CPU, so the script cannot rot between
chip runs.

The phases run with Pallas kernels in interpret mode.  The planner is
steered here the way a TPU steers it: the pallas backend, and the static
crossover constants (a TPU skips the CPU calibration table), with the
Krylov crossover lowered so a tiny matrix still takes the Krylov reduce.
"""

import functools
import importlib.util
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture()
def tpu_like_planner(monkeypatch):
    import repro.engine
    from repro.engine import autotune, engine, plan, server

    monkeypatch.setattr(autotune, "get_table", lambda: None)
    monkeypatch.setattr(plan, "KRYLOV_N_MIN", 128)
    pallas_plan_for = functools.partial(plan.plan_for, backend="pallas")
    for mod in (repro.engine, engine, server):
        monkeypatch.setattr(mod, "plan_for", pallas_plan_for)


def _assert_clean(smoke, record):
    assert record["plans"]
    assert smoke.problems(record) == []


def test_phase_served_tiny(smoke, tpu_like_planner):
    record = smoke.phase_served(0, ns=(72, 136), ks=(2, 4), per_cell=2,
                                full_n=72, full_k=4, full_count=2,
                                max_batch=4)
    _assert_clean(smoke, record)
    methods = {(p["method"], p["spectrum"]) for p in record["plans"]}
    assert methods == {("eei_tridiag", "windowed"), ("eei_krylov", "windowed"),
                       ("eei_tridiag", "full")}
    assert record["counters"]["requests"] == 18
    assert record["oracle"]["checked"] == 18


def test_phase_large_tiny(smoke, tpu_like_planner):
    record = smoke.phase_large(0, n=192, k=4)
    _assert_clean(smoke, record)
    assert record["plans"][0]["method"] == "eei_krylov"


def test_phase_session_tiny(smoke, tpu_like_planner):
    record = smoke.phase_session(0, n=136, k=4, updates=3)
    _assert_clean(smoke, record)
    assert record["oracle"]["checked"] == 3
    assert record["counters"]["fast_updates"] == 3


def test_spiked_matrix_has_its_spectrum(smoke):
    import numpy as np

    a, lam, vec = smoke.spiked_matrix(1, 64, 4)
    np.testing.assert_allclose(np.linalg.eigvalsh(a)[-4:], lam, atol=1e-12)
    np.testing.assert_allclose(vec @ a, lam[:, None] * vec, atol=1e-12)


def test_problems_flag_faults_and_backend(smoke):
    record = {"plans": [{"method": "eei_tridiag", "backend": "jnp",
                         "spectrum": "windowed"}],
              "counters": {"requests_degraded": 1, "host_reseeds": 2,
                           "requests": 5},
              "oracle": {"eig_err": 1.0, "residual": 0.0, "verify_ok": True}}
    found = smoke.problems(record)
    assert any("backend=jnp" in p for p in found)
    assert "requests_degraded=1" in found and "host_reseeds=2" in found
    assert any(p.startswith("oracle eig_err") for p in found)
    assert not any(p.startswith("requests=") for p in found)


_FOUR_CHIP_SCRIPT = """
import importlib.util, json, sys
spec = importlib.util.spec_from_file_location("chip_smoke", sys.argv[1])
smoke = importlib.util.module_from_spec(spec)
spec.loader.exec_module(smoke)
record = smoke.phase_four_chips(0, n=136, k=4, b=8)
print(json.dumps({"record": record,
                  "problems": smoke.problems(record, "sharded")}))
"""


def test_phase_four_chips_on_forced_host_devices():
    """The --four-chips phase on four virtual CPU devices: the output spans
    the mesh and matches the one-device server and the oracle."""
    import json

    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=str(ROOT / "src") + os.pathsep
               + os.environ.get("PYTHONPATH", ""))
    proc = subprocess.run(
        [sys.executable, "-c", _FOUR_CHIP_SCRIPT, str(ROOT / "chip_smoke.py")],
        env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stdout + "\n" + proc.stderr
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["problems"] == []
    assert out["record"]["counters"]["output_devices"] == 4
    assert out["record"]["plans"][0]["backend"] == "sharded"


def test_main_refuses_cpu(smoke, capsys):
    assert smoke.main([]) != 0
    captured = capsys.readouterr()
    assert "TPU" in captured.err
    assert captured.out == ""


def test_script_alone_fails_without_result(tmp_path):
    shutil.copy(ROOT / "chip_smoke.py", tmp_path / "chip_smoke.py")
    proc = subprocess.run([sys.executable, "chip_smoke.py"], cwd=tmp_path,
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0
    assert proc.stdout == ""
