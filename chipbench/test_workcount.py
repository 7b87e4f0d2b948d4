"""The work counts behind the roofline shares come from logical shapes and
stored dtypes: the same whether a plan takes the Pallas kernel or the jnp
path, and equal to the formulas written in ``pipelines/<P>.py``."""
import numpy as np
import pytest

from harness import load_module
from conftest import BENCH


def _pipe(name):
    return load_module(BENCH / "pipelines" / f"{name}.py", f"wc_{name}")


def _scene(rows, cols, bands):
    from repro.raster.sources import ArraySource

    return ArraySource(np.broadcast_to(np.zeros((), np.uint16), (rows, cols, bands)))


PARAMS = {
    "P2": {"radius": 2, "levels": 8, "offset": [0, 1], "vmin": 0, "vmax": 4096},
    "P3": {"ratio": 4, "radius": 2, "method": "bicubic"},
}


def _build(name, use_pallas):
    from repro.raster import MemoryMapper

    pipe = _pipe(name)
    if name == "P2":
        from repro import pipelines as PP

        pair = PP.p2_textures(_scene(32, 48, 4), use_pallas=use_pallas)
    else:
        from repro import pipelines as PP

        pair = PP.p3_pansharpening(_scene(8, 12, 4), _scene(32, 48, 1),
                                   use_pallas=use_pallas)
    del MemoryMapper
    return pipe, pair


@pytest.mark.parametrize("name", ["P2", "P3"])
def test_count_is_the_same_on_the_kernel_and_jnp_paths(name):
    from repro.core.region import ImageRegion

    counts, fused = [], []
    for use_pallas in (True, False):
        pipe, (p, m) = _build(name, use_pallas)
        desc = p.describe_pull(m, ImageRegion((8, 0), (8, p.info(m).cols)),
                               virtual=p.virtual_describe_mode())
        fused.append(bool(desc.pallas_nodes))
        params = PARAMS[name]
        counts.append((pipe.work((p, m), params),
                       {k: f((p, m), params) for k, f in pipe.KERNELS.items()}))
    assert fused == [True, False]  # the two plans really differ
    assert counts[0] == counts[1]


def test_counts_match_the_written_formulas():
    _, pair = _build("P2", None)
    assert _pipe("P2").work(pair, PARAMS["P2"]) == (15 + 3 * 25 + 14 * 64, 22.0)
    pipe, pair = _build("P3", None)
    ops, byts = pipe.work(pair, PARAMS["P3"])
    assert byts == 2 + 4 * 2 / 16 + 4 * 4
    assert ops == pytest.approx(7 * 4 * (1 / 4 + 1) + 25 + 2 + 4)
    assert pipe.KERNELS["pansharpen_rcs"](pair, PARAMS["P3"]) == (31.0, 34.0)
    from repro import pipelines as PP

    p6 = PP.p6_conversion(_scene(16, 16, 4))
    assert _pipe("P6").work(p6, {}) == (20.0, 12.0)
