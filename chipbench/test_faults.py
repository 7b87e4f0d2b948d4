"""The output check fails a run whose timed path is broken underneath, and
its control (the reference in bfloat16) fails the limit too.

Each fault is planted in the program below the harness, and the rest of a
run (set-up, window, check) runs as on the chip, on tiny scenes on the CPU:

- an answer altered where it is produced (one pixel of every strip, in the
  kernel's or the filter's output);
- half of the strips left out of each pass;
- the exchange between chips left out (tile grid, four CPU devices, in a
  child process).
"""
import json
import os
import subprocess
import sys
import textwrap

import jax
import pytest

import harness
from conftest import BENCH, ROOT

CELLS = ["s2-textures", "spot6-pansharpen", "s2-convert"]


def _altered(fn):
    """Wrap a region body so that its first value is wrong."""
    def body(*a, **k):
        out = fn(*a, **k)
        return out.at[0, 0, 0].set(out[0, 0, 0] * 2 + 7)
    return body


def _alter_answers(monkeypatch, cell):
    from repro.filters import Convert, HaralickTextures, PansharpenFuse

    if cell == "s2-convert":
        gen = Convert.generate
        monkeypatch.setattr(Convert, "generate",
                            lambda self, r, x: _altered(gen)(self, r, x))
        monkeypatch.setattr(Convert, "pointwise_fn", lambda self: None)
        return
    cls = HaralickTextures if cell == "s2-textures" else PansharpenFuse
    body = cls.pallas_body
    monkeypatch.setattr(cls, "pallas_body", lambda self, *a: _altered(body(self, *a)))


def _drop_half(monkeypatch, cell):
    from repro.core import StripeSplitter

    split = StripeSplitter.split
    monkeypatch.setattr(StripeSplitter, "split",
                        lambda self, region, info: split(self, region, info)[::2])


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct(tiny_root, cell):
    res = harness.run(tiny_root, cell, 3, 0.3, False, jax.devices(), 0.0)
    assert res["correct"], res["checks"]


@pytest.mark.parametrize("fault", [_alter_answers, _drop_half], ids=["altered", "dropped"])
@pytest.mark.parametrize("cell", CELLS)
def test_broken_path_is_not_correct(tiny_root, monkeypatch, cell, fault):
    fault(monkeypatch, cell)
    res = harness.run(tiny_root, cell, 3, 0.3, False, jax.devices(), 0.0)
    assert not res["correct"], res["checks"]


@pytest.mark.parametrize("cell", CELLS)
def test_control_fails_the_limit(tiny_root, cell):
    """The reference computed in bfloat16, in the program's place."""
    r = harness.Run(tiny_root, cell, 4, jax.devices(), 0.0)
    r.setup()
    r.window(0.3, False)
    r.free()
    assert r.check() <= r.cell.traffic["check"]["limit"]
    assert r.check("bfloat16") > r.cell.traffic["check"]["limit"]


GRID_RUN = textwrap.dedent("""
    import json, sys
    sys.path[:0] = [{bench!r}, {src!r}]
    import jax, harness
    from repro.core import parallel
    if {broken!r}:  # every chip edge-pads its own tile: nothing is exchanged
        rows, cols = parallel.halo_exchange_rows, parallel.halo_exchange_cols
        parallel.halo_exchange_rows = lambda x, a, b, ax, n: rows(x, a, b, ax, 1)
        parallel.halo_exchange_cols = lambda x, a, b, ax, n: cols(x, a, b, ax, 1)
    res = harness.run({root!r}, "s2-textures-2x2", 3, 0.3, False, jax.devices(), 0.0)
    print(json.dumps(res["checks"]), res["correct"])
""")


#: the tile-grid cell, added as a later PR would add it once it is proven
#: on four chips (its configuration file is ``configs/s2-l2a-2x2.json``)
GRID_CONFIG = {"name": "s2-l2a-2x2", "source": "test", "reduced": [],
               "file": "chipbench/configs/s2-l2a-2x2.json", "why": "test"}
GRID_CELL = {"name": "s2-textures-2x2", "config": "s2-l2a-2x2", "traffic": "textures",
             "chips": 4, "why": "test"}


@pytest.mark.parametrize("broken", [False, True], ids=["sound", "no-exchange"])
def test_grid_without_the_exchange_is_not_correct(tiny_root, broken):
    spec = json.loads((tiny_root / "BENCHMARK.json").read_text())
    spec["configs"].append(GRID_CONFIG)
    spec["workloads"].append(GRID_CELL)
    (tiny_root / "BENCHMARK.json").write_text(json.dumps(spec))
    code = GRID_RUN.format(bench=str(BENCH), src=str(ROOT / "src"),
                           root=str(tiny_root), broken=broken)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    proc = subprocess.run([sys.executable, "-c", code], env=env, capture_output=True,
                          text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    checks, correct = proc.stdout.strip().splitlines()[-1].rsplit(" ", 1)
    assert correct == ("False" if broken else "True"), checks
