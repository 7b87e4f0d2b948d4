"""P3 pansharpening streamed from two stored tiled files against a plain
float64 numpy reference written here (no program code, no kernels).

The reference follows the pipeline's definition: XS is resampled ×4 onto
the PAN grid with Keys bicubic weights (a = -0.5), pixel centres aligned and
taps clamped at the edges; then ``out_b = XS↑_b · PAN / box₅(PAN)``, the box
a 5 × 5 mean of the edge-replicated PAN.  The scene is sized so that the
strips' XS footprints straddle strip seams, the last strip is ragged and
neither width is a multiple of the 16-pixel storage tile.
"""
import numpy as np
import pytest

from repro import pipelines as PP
from repro.core import ImageInfo, PlanCache, StripeSplitter, whole
from repro.raster import TiledSource, TileWriter

RATIO, RADIUS = 4, 2
XS_ROWS, XS_COLS = 11, 13  # PAN 44 × 52: 52 is not a multiple of 16
STRIP = 12  # PAN rows: strips 12, 12, 12, 8, each reading ~7 XS rows


def _store(path, data):
    w = TileWriter(str(path), tile_rows=16)
    w.begin(ImageInfo(*data.shape, data.dtype))
    w.consume(whole(*data.shape[:2]), data)
    w.end()


def _keys(n_out, n_in):
    """(n_out, 4) tap indices and float64 Keys weights along one axis."""
    pos = (np.arange(n_out) + 0.5) / RATIO - 0.5
    base = np.floor(pos)
    d = np.abs(np.stack([pos - (base + k) for k in (-1, 0, 1, 2)], -1))
    a = -0.5
    w = np.where(d <= 1, (a + 2) * d**3 - (a + 3) * d**2 + 1,
                 np.where(d < 2, a * d**3 - 5 * a * d**2 + 8 * a * d - 4 * a, 0.0))
    idx = np.clip(base.astype(int)[:, None] + np.arange(-1, 3), 0, n_in - 1)
    return idx, w


def _reference(xs, pan):
    xs, pan = xs.astype(np.float64), pan[..., 0].astype(np.float64)
    ri, rw = _keys(pan.shape[0], xs.shape[0])
    ci, cw = _keys(pan.shape[1], xs.shape[1])
    up = np.einsum("rk,rkcb->rcb", rw, xs[ri])
    up = np.einsum("ck,rckb->rcb", cw, up[:, ci])
    padded = np.pad(pan, RADIUS, mode="edge")
    k = 2 * RADIUS + 1
    box = sum(padded[u:u + pan.shape[0], v:v + pan.shape[1]]
              for u in range(k) for v in range(k)) / (k * k)
    return up * (pan / box)[..., None]


@pytest.mark.parametrize("use_pallas", [True, False], ids=["pallas", "jnp"])
def test_streamed_p3_from_stored_files_matches_float64_reference(tmp_path, use_pallas):
    rng = np.random.default_rng(15)
    xs = rng.integers(0, 4096, (XS_ROWS, XS_COLS, 4), np.uint16)
    pan = rng.integers(1, 4096, (XS_ROWS * RATIO, XS_COLS * RATIO, 1), np.uint16)
    _store(tmp_path / "xs.rtic", xs)
    _store(tmp_path / "pan.rtic", pan)
    srcs = [TiledSource(str(tmp_path / n)) for n in ("xs.rtic", "pan.rtic")]
    try:
        pair = PP.p3_pansharpening(*srcs, ratio=RATIO, use_pallas=use_pallas)
        res, m = PP.run_pipeline(pair, executor="streaming", plan_cache=PlanCache(),
                                 splitter=StripeSplitter(stripe_rows=STRIP))
    finally:
        for s in srcs:
            s.close()
    assert res.cache_stats is not None  # the compiled plan path ran
    assert res.regions_processed == -(-pan.shape[0] // STRIP)
    got, want = m.result.astype(np.float64), _reference(xs, pan)
    assert got.shape == want.shape == (XS_ROWS * RATIO, XS_COLS * RATIO, 4)
    # float32 against float64: each output passes through about 40 float32
    # roundings (8 products and sums of the resample, 25 box sums, the
    # ratio and the product), each at most 2^-24 of the values' scale, so
    # the error stays below 64 · 2^-24 of the output's largest magnitude
    # (about 0.04 here; 1e-3 is measured).  A bfloat16 computation (2^-8)
    # or a pixel off by one would miss it by orders of magnitude.
    scale = np.abs(want).max()
    np.testing.assert_allclose(got, want, rtol=0, atol=64 * 2.0**-24 * scale)
