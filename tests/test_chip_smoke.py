"""chip_smoke.py's host-side pieces, on the CPU: the report-only series
comparison, the gates, and the refusal to run without a TPU."""
import importlib.util
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_compare_series_reports_a_2d_difference(smoke, capsys):
    a = np.zeros((4, 6), np.float32)
    b = a.copy()
    b[2, 5] = 3.0
    b[1, 0] = 1.0
    smoke.compare_series("fleet emissions", a, b)
    out = capsys.readouterr().out
    assert "DIFFERS at 2 of 24 entries" in out
    assert "max |diff| 3.0 at index (2, 5)" in out


@pytest.mark.parametrize("b,want", [
    (np.arange(6.0), "equal (6 entries)"),
    (np.arange(5.0), "DIFFERS in shape, (6,) vs (5,)"),
])
def test_compare_series_equal_and_shape(smoke, capsys, b, want):
    smoke.compare_series("s", np.arange(6.0), b)
    assert want in capsys.readouterr().out


def test_gates_name_every_failure(smoke, capsys):
    gates = smoke.Gates()
    gates.equal("same", np.ones((2, 3)), np.ones((2, 3)))
    gates.equal("shape", np.ones((2, 3)), np.ones((3, 2)))
    gates.equal("values", np.zeros((2, 3)), np.eye(2, 3))
    gates.kernel("kernel", "no custom call here")
    with pytest.raises(AssertionError) as e:
        gates.close()
    msg = str(e.value)
    assert "same" not in msg
    assert "shapes (2, 3) vs (3, 2)" in msg
    assert "2 of 6 entries differ" in msg
    assert "tpu_custom_call absent" in msg


def test_refuses_to_run_without_a_tpu(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    r = subprocess.run([sys.executable, str(ROOT / "chip_smoke.py")],
                       env=env, capture_output=True, text=True,
                       timeout=120)
    assert r.returncode != 0
    assert "not a TPU" in r.stderr
    assert '"ok"' not in r.stdout
