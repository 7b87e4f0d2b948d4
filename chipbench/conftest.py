"""Test set-up for ``python -m pytest chipbench`` (CPU, tiny scenes).

``tiny_root`` copies the benchmark into a temporary checkout whose
configurations keep their keys but hold scenes of a few dozen pixels, so a
whole run (set-up, window, check) takes seconds on the CPU with the Pallas
kernels in interpret mode.
"""
from __future__ import annotations

import json
import os
import shutil
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
for p in (str(BENCH), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

#: tiny sizes per configuration key (everything else stays as committed)
TINY = {
    "scene_rows": 40, "scene_cols": 70, "xs_rows": 12, "xs_cols": 16,
    "pan_rows": 48, "pan_cols": 64, "storage_tile": 16, "stripe_rows": 16,
}
RUNTIME = ("scenes", "traces", ".jax_cache", "__pycache__")
#: a cell whose files are in place but which waits for its chip runs; the
#: test copy lists it, so that its output check stays tested
WAITING = (
    {"name": "spot6-pms", "source": "test", "reduced": [], "why": "test",
     "file": "chipbench/configs/spot6-pms.json"},
    {"name": "spot6-pansharpen", "config": "spot6-pms", "traffic": "pansharpen",
     "chips": 1, "why": "test"},
)


def make_tiny_root(dest: Path) -> Path:
    shutil.copytree(BENCH, dest / "chipbench", ignore=shutil.ignore_patterns(*RUNTIME))
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    config, cell = WAITING
    if cell["name"] not in {w["name"] for w in spec["workloads"]}:
        spec["configs"].append(config)
        spec["workloads"].append(cell)
    (dest / "BENCHMARK.json").write_text(json.dumps(spec))
    os.symlink(ROOT / "src", dest / "src")
    for cfg in (dest / "chipbench" / "configs").glob("*.json"):
        data = json.loads(cfg.read_text())
        for k, v in TINY.items():
            if k in data:
                data[k] = v
        cfg.write_text(json.dumps(data))
    # the trace reduction needs peaks for the device it ran on; these are
    # the test copy's alone, so a CPU run can exercise it
    peaks = json.loads((dest / "chipbench" / "peaks.json").read_text())
    peaks["cpu"] = {"flops_per_s": 1e12, "bytes_per_s": 1e11, "source": "test only"}
    (dest / "chipbench" / "peaks.json").write_text(json.dumps(peaks))
    for traffic in (dest / "chipbench" / "traffic").glob("*.json"):
        data = json.loads(traffic.read_text())
        data["check"]["window_rows"] = 8
        traffic.write_text(json.dumps(data))
    return dest


@pytest.fixture
def tiny_root(tmp_path, monkeypatch):
    monkeypatch.setenv("REPRO_USE_PALLAS", "1")  # the kernels, interpreted
    return make_tiny_root(tmp_path)
