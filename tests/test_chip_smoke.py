"""chip_smoke.py: refuses to run off a TPU, and its phases hold on a tiny scene.

The phases run here in interpret mode (``REPRO_USE_PALLAS=1``), which is
exactly what the script itself refuses; they are called directly, below the
script's device checks.
"""
import os
import subprocess
import sys

import pytest

from conftest import REPO


def test_refuses_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, str(REPO / "chip_smoke.py")],
        capture_output=True, text=True, timeout=300, env=env, cwd=str(REPO),
    )
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
    assert "no TPU" in proc.stderr


@pytest.fixture
def chip_smoke(monkeypatch):
    monkeypatch.setenv("REPRO_USE_PALLAS", "1")
    monkeypatch.syspath_prepend(str(REPO))
    import chip_smoke

    return chip_smoke


def test_single_chip_phases_on_a_tiny_scene(chip_smoke, tmp_path, capsys):
    chip_smoke.single_chip(32, 300, 16, 16, 0, str(tmp_path))
    out = capsys.readouterr().out
    for name in ("P2", "P5", "P6", "P3"):
        assert f"{name}: scene" in out


def test_kernel_phase_fails_off_the_kernel_plan(chip_smoke, monkeypatch):
    from repro import pipelines as PP
    from repro.core import StripeSplitter
    from repro.raster import SyntheticScene

    monkeypatch.setenv("REPRO_USE_PALLAS", "0")
    scene = SyntheticScene(16, 128, bands=4)
    with pytest.raises(chip_smoke.SmokeFailure, match="did not take the Pallas"):
        chip_smoke.kernel_phase(
            "P5", lambda up: PP.p5_meanshift(scene, use_pallas=up),
            (16, 128, 4), StripeSplitter(stripe_rows=8),
        )
