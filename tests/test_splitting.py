"""Property tests: every splitter tiles the domain exactly (paper §II.B/D),
and the virtual tile-grid geometry behind the 2-D SPMD executor partitions
the padded plane with zero clamping.

Property tests run under hypothesis when it is installed (CI test extras);
without it each ``_check_*`` body still runs over a seeded random sample so
the geometry contract is exercised everywhere, just with fewer examples.
"""
import numpy as np
import pytest

try:  # CI installs hypothesis via the test extras; local runs may lack it
    from hypothesis import given, strategies as st

    HAVE_HYPOTHESIS = True
except ImportError:  # pragma: no cover
    HAVE_HYPOTHESIS = False

from repro.core import (
    AutoSplitter,
    ImageInfo,
    StripeSplitter,
    TileSplitter,
    VMEMTileSplitter,
    padded_tile_grid,
    virtual_tile_regions,
    whole,
)
from repro.core.splitting import (
    clamped_tile_spans,
    padded_strip_rows,
    virtual_strip_regions,
)


def assert_exact_cover(regions, full):
    cover = np.zeros((full.rows, full.cols), np.int32)
    for r in regions:
        assert full.contains(r), (r, full)
        rs, cs = r.slices()
        cover[rs, cs] += 1
    assert (cover == 1).all(), "regions must cover every pixel exactly once"


def _sample(seed, *ranges, n=25):
    """Seeded fallback sample of integer tuples, one per hypothesis range."""
    rng = np.random.default_rng(seed)
    out = [tuple(lo for lo, _ in ranges)]  # always include the all-min corner
    out += [tuple(int(rng.integers(lo, hi + 1)) for lo, hi in ranges)
            for _ in range(n - 1)]
    return out


def _property(seed, *ranges):
    """Run the check under hypothesis when present, else over a seeded
    deterministic sample (so the property is still exercised everywhere)."""

    def deco(check):
        if HAVE_HYPOTHESIS:
            strategies = [st.integers(lo, hi) for lo, hi in ranges]

            @given(st.tuples(*strategies))
            def wrapper(args):
                check(*args)

            return wrapper

        @pytest.mark.parametrize("args", _sample(seed, *ranges))
        def wrapper(args):
            check(*args)

        return wrapper

    return deco


# -- classic splitters: exact cover ------------------------------------------
@_property(1, (1, 80), (1, 80), (1, 12))
def test_stripe_splits_cover(rows, cols, n):
    info = ImageInfo(rows, cols, 3)
    full = whole(rows, cols)
    assert_exact_cover(StripeSplitter(n_splits=n).split(full, info), full)


@_property(2, (1, 80), (1, 80), (1, 20), (1, 20))
def test_tile_splits_cover(rows, cols, th, tw):
    info = ImageInfo(rows, cols, 1)
    full = whole(rows, cols)
    assert_exact_cover(TileSplitter(th, tw).split(full, info), full)


@_property(3, (1, 100), (1, 100), (64, 10_000), (1, 8))
def test_auto_splits_cover_and_fit(rows, cols, budget, workers):
    info = ImageInfo(rows, cols, 2, np.float32)
    full = whole(rows, cols)
    regions = AutoSplitter(budget, workers).split(full, info)
    assert_exact_cover(regions, full)
    # memory budget respected whenever a single row already fits
    if cols * info.bytes_per_pixel <= budget:
        for r in regions:
            assert r.num_pixels * info.bytes_per_pixel <= budget + cols * info.bytes_per_pixel


def test_auto_split_count_multiple_of_workers():
    info = ImageInfo(1000, 100, 1, np.float32)
    regions = AutoSplitter(40_000, n_workers=3).split(whole(1000, 100), info)
    assert len(regions) % 3 == 0


def test_vmem_tiles_aligned():
    info = ImageInfo(1000, 1000, 4, np.float32)
    regions = VMEMTileSplitter(2**20, align=128).split(whole(1000, 1000), info)
    assert_exact_cover(regions, whole(1000, 1000))
    interior = [r for r in regions if r.row1 < 1000 and r.col1 < 1000]
    assert all(r.rows % 128 == 0 and r.cols % 128 == 0 for r in interior)


# -- virtual tile-grid geometry (SPMD 2-D contract) ---------------------------
@_property(4, (1, 90), (1, 90), (1, 9), (1, 9))
def test_padded_tile_grid_invariants(rows, cols, nr, nc):
    Hr, Wc, pr, pc = padded_tile_grid(rows, cols, nr, nc)
    assert nr * Hr == rows + pr and nc * Wc == cols + pc
    # minimal padding: Hr/Wc are the smallest uniform tile dims, so the pad
    # is strictly less than one row/col per worker along each axis
    assert 0 <= pr < nr and 0 <= pc < nc
    assert (Hr - 1) * nr < rows and (Wc - 1) * nc < cols


@_property(5, (1, 90), (1, 90), (1, 9), (1, 9))
def test_virtual_tiles_disjoint_exact_cover(rows, cols, nr, nc):
    """The nr×nc virtual tiles partition the PADDED grid exactly — no gaps,
    no overlaps, every tile the same Hr×Wc shape (ragged splits included:
    edge tiles spill past the image instead of shrinking)."""
    Hr, Wc, pr, pc = padded_tile_grid(rows, cols, nr, nc)
    tiles = virtual_tile_regions(rows, cols, nr, nc)
    assert len(tiles) == nr * nc
    assert all(t.size == (Hr, Wc) for t in tiles)
    assert_exact_cover(tiles, whole(rows + pr, cols + pc))
    # row-major ordering: tile k covers grid cell (k // nc, k % nc)
    for k, t in enumerate(tiles):
        assert (t.row0, t.col0) == ((k // nc) * Hr, (k % nc) * Wc)


@_property(6, (1, 90), (1, 90), (1, 9), (1, 9))
def test_virtual_tiles_clamp_to_image_cover(rows, cols, nr, nc):
    """Clamping each virtual tile to the image yields an exact cover of the
    image itself — the crop the executor applies after the masked SPMD run."""
    full = whole(rows, cols)
    clipped = [t.intersect(full) for t in virtual_tile_regions(rows, cols, nr, nc)]
    assert_exact_cover([t for t in clipped if t is not None and t.num_pixels], full)


@_property(7, (1, 90), (1, 90), (1, 12))
def test_virtual_tiles_nc1_matches_strip_oracle(rows, cols, n):
    """The nc=1 column of the tile grid IS the legacy strip geometry: same
    regions, same padding, and the same interior/border classification
    (a tile spills past the image exactly when its strip did)."""
    strips = virtual_strip_regions(rows, cols, n)
    tiles = virtual_tile_regions(rows, cols, n, 1)
    assert tiles == strips
    H, pad = padded_strip_rows(rows, n)
    Hr, Wc, pr, pc = padded_tile_grid(rows, cols, n, 1)
    assert (Hr, pr) == (H, pad) and (Wc, pc) == (cols, 0)
    full = whole(rows, cols)
    strip_border = [not full.contains(s) for s in strips]
    tile_border = [not full.contains(t) for t in tiles]
    assert tile_border == strip_border


@_property(8, (0, 30), (1, 60), (1, 15))
def test_clamped_tile_spans_partition(lo, extent, step):
    """clamped_tile_spans tiles [lo, hi) exactly: contiguous, in order, every
    span full-width except possibly the last."""
    hi = lo + extent
    spans = clamped_tile_spans(lo, hi, step)
    assert spans[0][0] == lo
    assert all(a + s == b for (a, s), (b, _) in zip(spans, spans[1:]))
    a, s = spans[-1]
    assert a + s == hi
    assert all(s == step for _, s in spans[:-1]) and 0 < spans[-1][1] <= step


def test_tile_geometry_rejects_nonpositive():
    for bad in [(0, 4, 1, 1), (4, 0, 1, 1), (4, 4, 0, 1), (4, 4, 1, 0)]:
        with pytest.raises(ValueError):
            padded_tile_grid(*bad)
    with pytest.raises(ValueError):
        clamped_tile_spans(0, 10, 0)


# -- auto splitters: unit coverage beyond the cover property ------------------
def test_auto_splitter_validates_args():
    with pytest.raises(ValueError):
        AutoSplitter(0)
    with pytest.raises(ValueError):
        AutoSplitter(1024, n_workers=0)


def test_auto_splitter_budget_drives_split_count():
    info = ImageInfo(120, 100, 1, np.float32)  # 400 B/row
    full = whole(120, 100)
    # 4 kB budget -> 10 rows/split -> 12 splits (already a multiple of 1)
    regions = AutoSplitter(4_000, n_workers=1).split(full, info)
    assert len(regions) == 12
    assert all(r.rows <= 10 for r in regions)
    # a loose budget still yields one split per worker
    assert len(AutoSplitter(10**9, n_workers=4).split(full, info)) == 4


def test_auto_splitter_single_row_floor():
    # budget below one row: degrade to 1-row strips, never zero-size regions
    info = ImageInfo(7, 100, 4, np.float32)  # 1600 B/row
    regions = AutoSplitter(100, n_workers=2).split(whole(7, 100), info)
    assert_exact_cover(regions, whole(7, 100))
    assert all(r.rows == 1 for r in regions)


def test_vmem_splitter_align_floor_and_budget():
    info = ImageInfo(600, 600, 4, np.float32)  # 16 B/px
    # tiny budget: side floors at `align` even though align^2 overflows it
    regions = VMEMTileSplitter(2**10, align=64).split(whole(600, 600), info)
    assert_exact_cover(regions, whole(600, 600))
    assert max(max(r.rows, r.cols) for r in regions) <= 64
    # roomy budget: interior tiles stay inside the VMEM budget
    regions = VMEMTileSplitter(2**22, align=128).split(whole(600, 600), info)
    assert_exact_cover(regions, whole(600, 600))
    interior = [r for r in regions if r.row1 < 600 and r.col1 < 600]
    assert interior and all(
        r.num_pixels * info.bytes_per_pixel <= 2**22 for r in interior
    )
