"""A cell added as files plus a ``BENCHMARK.json`` entry runs with no edit
to a file that is there; the run refuses without a TPU; a checkout with
only the benchmark's files refuses too."""
import hashlib
import json
import os
import shutil
import subprocess
import sys

import jax

import harness


def _digests(root):
    return {
        p: hashlib.sha256(p.read_bytes()).hexdigest()
        for p in (root / "chipbench").rglob("*") if p.is_file()
    }


def test_a_cell_added_as_files_is_picked_up(tiny_root):
    before = _digests(tiny_root)
    bench = tiny_root / "chipbench"
    # a new traffic mix (P2 at radius 1), a new configuration, a new metric
    traffic = json.loads((bench / "traffic" / "textures.json").read_text())
    traffic["params"]["radius"] = 1
    (bench / "traffic" / "textures-r1.json").write_text(json.dumps(traffic))
    cfg = json.loads((bench / "configs" / "s2-l2a.json").read_text())
    cfg["scene_rows"] = 24
    (bench / "configs" / "s2-small.json").write_text(json.dumps(cfg))
    (bench / "metrics" / "passes_in_window.py").write_text(
        "def read(ctx):\n    return float(len(ctx.spans.of('pass')))\n"
    )
    spec = json.loads((tiny_root / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "s2-small", "source": "test", "reduced": ["scene_rows"],
                            "file": "chipbench/configs/s2-small.json", "why": "test"})
    spec["workloads"].append({"name": "s2-small.textures-r1", "config": "s2-small",
                              "traffic": "textures-r1", "chips": 1, "why": "test"})
    spec["per_layer"].append({"name": "passes_in_window", "unit": "count", "better": "higher",
                              "source": "program_span", "layer": "Entry", "moves": "mpx_per_s",
                              "workloads": ["s2-small.textures-r1"]})
    (tiny_root / "BENCHMARK.json").write_text(json.dumps(spec))

    cell = harness.Cell(tiny_root, "s2-small.textures-r1")
    assert cell.config["scene_rows"] == 24 and cell.traffic["params"]["radius"] == 1
    assert [m["name"] for m in cell.per_layer()][-1] == "passes_in_window"
    for trace in (False, True):
        res = harness.run(tiny_root, "s2-small.textures-r1", 5, 0.3, trace,
                          jax.devices(), 0.0)
        assert res["correct"], res
    assert set(res["metrics"]) == {"passes_in_window"}  # only what lists the cell
    after = _digests(tiny_root)
    assert {p: after[p] for p in before} == before  # no file that was there changed


def test_run_refuses_without_a_tpu(tiny_root):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", "s2-convert",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tiny_root, env=env, capture_output=True, text=True, timeout=300,
    )
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "no TPU" in proc.stderr


def test_run_refuses_with_only_the_benchmark_files(tmp_path, tiny_root):
    shutil.copytree(tiny_root / "chipbench", tmp_path / "alone" / "chipbench")
    shutil.copy(tiny_root / "BENCHMARK.json", tmp_path / "alone" / "BENCHMARK.json")
    proc = subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", "s2-convert",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path / "alone", capture_output=True, text=True, timeout=300,
        env=dict(os.environ, JAX_PLATFORMS="cpu"),
    )
    assert proc.returncode != 0 and proc.stdout.strip() == ""
