"""RTIC tiled container + the Source/Sink protocol (cloud-native IO).

Covers the PR-10 acceptance bars: TileWriter → TiledSource round trip
(property test over tile geometry × strip covers), stored overviews
bit-equal to on-the-fly decimation, the range-read backends (file + the
in-memory remote stand-in with request counters), async read-ahead,
DecimatedSource edge behavior (ragged clamping, origin rescaling), the
protocol coercers / capability flags / deprecated free-function wrappers,
``run_pipeline(sink=...)``, the catalog layer behind P8/P9, and the
streamed-then-SPMD zero-new-lowers guarantee over a TiledSource.
"""
import json
import os
import random
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np
import pytest

try:  # only the property test needs hypothesis; the rest must always run
    from hypothesis import given, settings, strategies as st

    HAVE_HYPOTHESIS = True
except ImportError:  # pragma: no cover
    HAVE_HYPOTHESIS = False

from repro import pipelines as PP
from repro.core import (
    ImageInfo,
    ImageRegion,
    Pipeline,
    StreamingExecutor,
    StripeSplitter,
    whole,
)
from repro.core.process_object import GeoTransform
from repro.core.region import tile_cover
from repro.raster import (
    CAP_PYRAMIDAL,
    CAP_RANGE_READABLE,
    CAP_TILED,
    ArraySource,
    DecimatedSource,
    MemoryRangeReader,
    MosaicSource,
    ParallelRasterWriter,
    RasterReader,
    SceneCatalog,
    SceneEntry,
    SyntheticScene,
    TiledSource,
    TileWriter,
    as_sink,
    as_source,
)
from repro.raster import io as rio


def _write_rtic(path, data, tile_rows=16, tile_cols=None, levels=None,
                strip_rows=7, geo=None):
    """Write ``data`` through TileWriter in full-width strips of
    ``strip_rows`` (the executors' consume pattern)."""
    rows, cols, bands = data.shape
    info = ImageInfo(
        rows, cols, bands, data.dtype,
        geo or GeoTransform(1.0, 2.0, 6.0, -6.0),
    )
    w = TileWriter(path, tile_rows, tile_cols, levels=levels)
    w.begin(info)
    r0 = 0
    while r0 < rows:
        h = min(strip_rows, rows - r0)
        w.consume(ImageRegion((r0, 0), (h, cols)), data[r0:r0 + h])
        r0 += h
    w.end()
    return info


def _rand(rows, cols, bands=3, dtype=np.float32, seed=0):
    rng = np.random.default_rng(seed)
    if np.issubdtype(np.dtype(dtype), np.integer):
        return rng.integers(0, 1000, size=(rows, cols, bands)).astype(dtype)
    return rng.normal(size=(rows, cols, bands)).astype(dtype)


# -- round trip ---------------------------------------------------------------

def test_roundtrip_exact(tmp_path):
    path = str(tmp_path / "a.rtic")
    data = _rand(50, 37, 3)
    info = _write_rtic(path, data, tile_rows=16, tile_cols=13)
    src = TiledSource(path)
    try:
        got = src.read_region()
        np.testing.assert_array_equal(got, data)
        out = src.info()
        assert (out.rows, out.cols, out.bands) == (50, 37, 3)
        assert out.geo.spacing_x == info.geo.spacing_x
        # windowed read straddling tile boundaries
        win = ImageRegion((10, 8), (23, 21))
        np.testing.assert_array_equal(
            src.read_region(win), data[10:33, 8:29]
        )
        # jax-side generate (the executor path) agrees with read_region
        np.testing.assert_array_equal(np.asarray(src.generate(win)),
                                      data[10:33, 8:29])
    finally:
        src.close()


if HAVE_HYPOTHESIS:

    @settings(max_examples=10, deadline=None)
    @given(
        st.integers(1, 60), st.integers(1, 50), st.integers(1, 20),
        st.integers(1, 20), st.integers(1, 13), st.booleans(),
    )
    def test_roundtrip_property(tmp_path_factory, rows, cols, tile_r,
                                tile_c, strip_rows, reverse):
        _check_roundtrip(tmp_path_factory, rows, cols, tile_r, tile_c,
                         strip_rows, reverse)

else:  # stay visible as a skip (not silently uncollected) without hypothesis

    @pytest.mark.skip(reason="property test needs hypothesis")
    def test_roundtrip_property():
        pass


# deterministic corner geometries — always run, with or without hypothesis
@pytest.mark.parametrize(
    "rows,cols,tile_r,tile_c,strip_rows,reverse",
    [
        (1, 1, 1, 1, 1, False),       # degenerate single pixel
        (33, 17, 8, 5, 4, True),      # ragged both axes, reverse order
        (60, 50, 20, 20, 13, False),  # strips never tile-aligned
        (10, 31, 16, 4, 3, True),     # tile taller than the image
    ],
)
def test_roundtrip_cases(tmp_path_factory, rows, cols, tile_r, tile_c,
                         strip_rows, reverse):
    _check_roundtrip(tmp_path_factory, rows, cols, tile_r, tile_c,
                     strip_rows, reverse)


def _check_roundtrip(tmp_path_factory, rows, cols, tile_r, tile_c,
                     strip_rows, reverse):
    tmp = tmp_path_factory.mktemp("rt")
    path = str(tmp / "p.rtic")
    data = _rand(rows, cols, bands=2, seed=rows * 61 + cols)
    info = ImageInfo(rows, cols, 2, data.dtype)
    w = TileWriter(path, tile_r, tile_c)
    w.begin(info)
    strips = []
    r0 = 0
    while r0 < rows:
        h = min(strip_rows, rows - r0)
        strips.append((ImageRegion((r0, 0), (h, cols)), data[r0:r0 + h]))
        r0 += h
    # consume order must not matter (every tile's byte range is fixed in
    # begin, and each region is written straight into the tiles it meets)
    for region, block in reversed(strips) if reverse else strips:
        w.consume(region, block)
    w.end()
    src = TiledSource(path)
    try:
        np.testing.assert_array_equal(src.read_region(), data)
        # every stored overview level equals the decimation contract
        flat = ArraySource(data)
        for lv in range(1, src._c.n_levels):
            np.testing.assert_array_equal(
                TiledSource(src._c, level=lv).read_region(),
                DecimatedSource(flat, 2 ** lv).read_region(),
            )
    finally:
        src.close()


def test_tile_unaligned_partial_covers(tmp_path):
    """Disjoint non-strip covers (2-D tiles smaller than the container's
    tile grid) still reassemble exactly: each block's rows land in place."""
    path = str(tmp_path / "t.rtic")
    data = _rand(21, 19, 2, seed=5)
    info = ImageInfo(21, 19, 2, data.dtype)
    w = TileWriter(path, tile_rows=8, tile_cols=8)
    w.begin(info)
    pieces = list(tile_cover(whole(21, 19), 5, 6, bounds=whole(21, 19)))
    for _, _, region in reversed(pieces):
        w.consume(region, data[region.slices()])
    w.end()
    src = TiledSource(path)
    try:
        np.testing.assert_array_equal(src.read_region(), data)
    finally:
        src.close()


def _oracle_file(data, tile_r, tile_c, levels, geo):
    """The expected RTIC file, built from the whole array: the header, the
    tiles of every level in level-major, row-major order, the index."""
    rows, cols, bands = data.shape
    if levels is None:  # add levels until the coarsest fits one tile, <= 9
        levels = 1
        while levels < 9 and max(
            -(-rows // 2 ** (levels - 1)), -(-cols // 2 ** (levels - 1))
        ) > max(tile_r, tile_c):
            levels += 1
    blobs, index, offset = [], [], 4096
    for lv in range(levels):
        img = data[:: 2 ** lv, :: 2 ** lv]
        tiles = {}
        for ty in range(-(-img.shape[0] // tile_r)):
            for tx in range(-(-img.shape[1] // tile_c)):
                blob = img[ty * tile_r:(ty + 1) * tile_r,
                           tx * tile_c:(tx + 1) * tile_c].tobytes()
                tiles[f"{ty},{tx}"] = [offset, len(blob)]
                blobs.append(blob)
                offset += len(blob)
        index.append({"rows": img.shape[0], "cols": img.shape[1],
                      "tiles": tiles})
    payload = json.dumps({"levels": index}).encode()
    meta = {
        "rows": rows, "cols": cols, "bands": bands, "dtype": data.dtype.str,
        "geo": [geo.origin_x, geo.origin_y, geo.spacing_x, geo.spacing_y],
        "nodata": None, "tile_rows": tile_r, "tile_cols": tile_c,
        "levels": levels, "index_offset": offset,
        "index_length": len(payload),
    }
    head = (b"RTIC0001" + json.dumps(meta).encode()).ljust(4096, b"\0")
    return head + b"".join(blobs) + payload


def _cover(kind, rows, cols, tile_r):
    """Disjoint regions covering a rows x cols image."""
    full = whole(rows, cols)
    if kind == "whole":
        return [full]
    if kind in ("strips", "device", "padded", "flipped", "threads"):  # full width
        h = tile_r + 3 if kind != "threads" else max(1, tile_r // 2 + 1)
        return [ImageRegion((r, 0), (min(h, rows - r), cols))
                for r in range(0, rows, h)]
    blocks = [t for _, _, t in tile_cover(full, 5, 7, bounds=full)]
    random.Random(rows * 31 + cols).shuffle(blocks)  # unaligned, shuffled
    return blocks


def _padded(a):
    """``a`` with its rows a padded row apart, as the plan entry hands a
    strip to the host."""
    rows, cols, bands = a.shape
    buf = np.zeros((rows, (cols + 3) * bands), a.dtype)
    buf[:, : cols * bands] = a.reshape(rows, -1)
    return buf[:, : cols * bands].reshape(a.shape)


@pytest.mark.parametrize(
    "cover", ["whole", "strips", "device", "padded", "flipped", "blocks", "threads"]
)
@pytest.mark.parametrize(
    "rows,cols,tile_r,tile_c,bands,dtype,levels",
    [
        (37, 29, 8, 8, 1, np.uint8, None),      # ragged right and bottom
        (50, 37, 16, 13, 3, np.float32, 3),     # tile_rows != tile_cols
        (64, 48, 16, 16, 5, np.uint16, 1),      # aligned, full resolution only
        (23, 61, 8, 12, 2, np.float32, None),   # wide, auto levels
        (1, 1, 4, 4, 4, np.uint8, 2),           # one pixel
        (45, 34, 32, 7, 4, np.uint16, 2),       # tile taller than a strip
    ],
)
def test_file_bytes_match_oracle_for_every_cover(
    tmp_path, cover, rows, cols, tile_r, tile_c, bands, dtype, levels
):
    """Any disjoint cover, in any order or from several threads at once,
    gives the oracle's file byte for byte.  ``device`` and ``threads`` hand
    over column-major strips, the layout a TPU's rank-3 strips come back
    in; ``padded`` strips with C-ordered rows a padded row apart;
    ``flipped`` strips whose rows run backwards in memory."""
    data = _rand(rows, cols, bands, dtype=dtype, seed=rows + cols + bands)
    geo = GeoTransform(1.0, 2.0, 6.0, -6.0)
    path = str(tmp_path / "c.rtic")
    w = TileWriter(path, tile_r, tile_c, levels=levels)
    w.begin(ImageInfo(rows, cols, bands, data.dtype, geo))
    regions = _cover(cover, rows, cols, tile_r)
    layout = {"device": np.asfortranarray, "threads": np.asfortranarray,
              "padded": _padded, "flipped": lambda a: a[::-1].copy()[::-1]
              }.get(cover, np.asarray)
    if cover == "threads":
        with ThreadPoolExecutor(4) as pool:
            list(pool.map(
                lambda r: w.consume(r, layout(data[r.slices()])), regions
            ))
    else:
        for region in regions:
            w.consume(region, layout(data[region.slices()]))
    w.end()
    with open(path, "rb") as f:
        got = f.read()
    assert got == _oracle_file(data, tile_r, tile_c, levels, geo)


def test_under_covered_pixels_read_zero(tmp_path):
    """Pixels no region covered read back as zero at every level, and the
    file opens even with whole tiles never touched."""
    data = _rand(40, 35, 2, dtype=np.uint16, seed=12)
    path = str(tmp_path / "u.rtic")
    w = TileWriter(path, tile_rows=8, tile_cols=8)
    w.begin(ImageInfo(40, 35, 2, data.dtype))
    want = np.zeros_like(data)
    for region in (ImageRegion((0, 0), (13, 35)), ImageRegion((20, 5), (9, 11)),
                   ImageRegion((33, 30), (7, 5))):
        w.consume(region, data[region.slices()])
        want[region.slices()] = data[region.slices()]
    w.end()
    src = TiledSource(path)
    try:
        assert src._c.n_levels == 4
        for lv in range(src._c.n_levels):
            np.testing.assert_array_equal(
                TiledSource(src._c, level=lv).read_region(),
                want[:: 2 ** lv, :: 2 ** lv],
            )
    finally:
        src.close()


# -- overviews ----------------------------------------------------------------

def test_overview_levels_match_decimated(tmp_path):
    path = str(tmp_path / "o.rtic")
    data = _rand(70, 45, 2, seed=3)
    _write_rtic(path, data, tile_rows=16, levels=3)
    src = TiledSource(path)
    try:
        assert src.overview(0) is src
        flat = ArraySource(data)
        for lv in (1, 2):
            ov = src.overview(lv)
            assert isinstance(ov, TiledSource)
            np.testing.assert_array_equal(
                ov.read_region(),
                DecimatedSource(flat, 2 ** lv).read_region(),
            )
            # level info scales geo spacing by 2**lv
            assert ov.info().geo.spacing_x == src.info().geo.spacing_x * 2 ** lv
        # past the deepest stored level: decimate the deepest level; the
        # ceil-division composition keeps the pixel contract exact
        ov3 = src.overview(3)
        assert isinstance(ov3, DecimatedSource)
        np.testing.assert_array_equal(
            ov3.read_region(), DecimatedSource(flat, 8).read_region()
        )
        # an overview view of an overview composes levels
        np.testing.assert_array_equal(
            src.overview(1).overview(1).read_region(),
            DecimatedSource(flat, 4).read_region(),
        )
    finally:
        src.close()


def test_auto_level_selection(tmp_path):
    """Default pyramid depth: add levels until the coarsest fits one tile."""
    path = str(tmp_path / "auto.rtic")
    _write_rtic(path, _rand(100, 80, 1), tile_rows=16)
    src = TiledSource(path)
    try:
        # 100x80 → 50x40 → 25x20 → 13x10 (fits 16x16): 4 levels
        assert src._c.n_levels == 4
        lv = src._c.levels[-1]
        assert max(lv["rows"], lv["cols"]) <= 16
    finally:
        src.close()


def test_zoom_view_routes_through_overview(tmp_path):
    from repro.serve.tiles import zoom_view

    path = str(tmp_path / "z.rtic")
    data = _rand(64, 48, 2, seed=9)
    _write_rtic(path, data, tile_rows=16, levels=2)
    src = TiledSource(path)
    try:
        assert zoom_view(src, 0) is src
        z1 = zoom_view(src, 1)
        assert isinstance(z1, TiledSource)  # stored level, not a wrap
        np.testing.assert_array_equal(z1.read_region(), data[::2, ::2])
        # non-pyramidal sources fall back to DecimatedSource
        flat = ArraySource(data)
        zf = zoom_view(flat, 1)
        assert isinstance(zf, DecimatedSource)
        np.testing.assert_array_equal(zf.read_region(), data[::2, ::2])
    finally:
        src.close()


# -- range backends + read-ahead ----------------------------------------------

def test_memory_range_reader_counts_requests(tmp_path):
    path = str(tmp_path / "m.rtic")
    data = _rand(40, 40, 1, seed=2)
    _write_rtic(path, data, tile_rows=16, levels=1)
    reader = MemoryRangeReader.from_file(path)
    src = TiledSource(reader)
    try:
        base = reader.requests  # header + footer index
        assert base == 2
        win = ImageRegion((0, 0), (10, 10))  # one tile
        np.testing.assert_array_equal(src.read_region(win), data[:10, :10])
        assert reader.requests == base + 1
        # cached tile: a second read costs zero range requests
        np.testing.assert_array_equal(src.read_region(win), data[:10, :10])
        assert reader.requests == base + 1
        assert src.stats()["tile_hits"] >= 1
        # whole image: 3x3 tile grid, 8 more fetches
        np.testing.assert_array_equal(src.read_region(), data)
        assert reader.requests == base + 9
        assert reader.bytes_read > 0
    finally:
        src.close()
    assert src._c.owns_reader is False


def test_file_range_reader_stats(tmp_path):
    path = str(tmp_path / "f.rtic")
    data = _rand(20, 20, 1)
    _write_rtic(path, data, tile_rows=16, levels=1)
    src = TiledSource(path)  # FileRangeReader under the hood
    try:
        np.testing.assert_array_equal(src.read_region(), data)
        s = src.stats()
        assert s["requests"] >= 2 + 4  # header + index + 2x2 tiles
        assert s["tile_misses"] == 4
    finally:
        src.close()


def test_read_ahead_prefetches_tiles(tmp_path):
    path = str(tmp_path / "ra.rtic")
    data = _rand(48, 32, 2, seed=4)
    _write_rtic(path, data, tile_rows=16, levels=1)
    reader = MemoryRangeReader.from_file(path)
    src = TiledSource(reader)
    try:
        regions = [ImageRegion((r, 0), (12, 32)) for r in (0, 12, 24, 36)]
        n = src.read_ahead(regions)
        assert n == 6  # 3x2 tile grid, deduplicated across regions
        assert src.stats()["readahead_scheduled"] == 6
        src._c.drain()
        deadline = time.monotonic() + 2.0
        while (src.stats()["cached_tiles"] < 6
               and time.monotonic() < deadline):
            time.sleep(0.005)
        assert src.stats()["cached_tiles"] == 6
        # re-scheduling cached tiles is a no-op
        assert src.read_ahead(regions) == 0
        hits0 = src.stats()["tile_hits"]
        for region in regions:
            np.testing.assert_array_equal(
                src.read_region(region), data[region.slices()]
            )
        assert src.stats()["tile_hits"] >= hits0 + 6
    finally:
        src.close()


def test_rejects_out_of_image_and_bad_magic(tmp_path):
    path = str(tmp_path / "b.rtic")
    data = _rand(20, 20, 1)
    _write_rtic(path, data, tile_rows=16)
    src = TiledSource(path)
    try:
        with pytest.raises(ValueError):
            src.read_region(ImageRegion((10, 10), (20, 20)))
        with pytest.raises(ValueError):
            TiledSource(src._c, level=9)
    finally:
        src.close()
    flat = str(tmp_path / "x.rtif")
    rio.create(flat, ImageInfo(4, 4, 1, np.uint8))
    with pytest.raises(ValueError):
        TiledSource(flat)


# -- DecimatedSource edge behavior (satellite: zoom-view correctness) ---------

def test_decimated_ragged_edges_and_origins():
    base = SyntheticScene(29, 23, bands=2, dtype=np.float32, seed=1)
    full = np.asarray(base.generate(whole(29, 23)))
    d = DecimatedSource(base, 4)
    info = d.output_info()
    # ceil-division dims: the ragged last row/col of samples is kept
    assert (info.rows, info.cols) == (8, 6)
    assert info.geo.spacing_x == base.output_info().geo.spacing_x * 4
    got = d.read_region()
    np.testing.assert_array_equal(got, full[::4, ::4])
    # ragged bottom-right window: the scaled base window clamps to the
    # image (rows 24..29 from a nominal 24..32) and still yields 2x2
    win = ImageRegion((6, 4), (2, 2))
    np.testing.assert_array_equal(d.read_region(win), got[6:8, 4:6])
    # origin rescaling: a needs_origin base samples absolute coordinates,
    # so every windowed read equals the matching full-read slice
    for win in (ImageRegion((0, 0), (3, 3)), ImageRegion((5, 1), (3, 5)),
                ImageRegion((7, 5), (1, 1))):
        np.testing.assert_array_equal(d.read_region(win), got[win.slices()])


def test_decimated_overview_composes_factors():
    base = SyntheticScene(57, 41, bands=1, dtype=np.float32, seed=2)
    d2 = DecimatedSource(base, 2)
    ov = d2.overview(1)
    # one flat strided view of the base, not a nested wrap
    assert isinstance(ov, DecimatedSource) and ov.base is base
    assert ov.factor == 4
    np.testing.assert_array_equal(
        ov.read_region(), DecimatedSource(base, 4).read_region()
    )
    # ceil-division composes: nested view pixels are identical
    nested = DecimatedSource(d2, 2)
    np.testing.assert_array_equal(ov.read_region(), nested.read_region())
    assert d2.overview(0) is d2


# -- protocol surface ---------------------------------------------------------

def test_capabilities():
    scene = SyntheticScene(8, 8, bands=1, dtype=np.float32)
    assert scene.capabilities() == frozenset()  # the protocol default
    assert TileWriter("x.rtic").capabilities() == {CAP_TILED, CAP_PYRAMIDAL}
    assert MemoryRangeReader(b"").size() == 0  # remote stand-in is trivial


def test_as_source_sniffs_container_magic(tmp_path):
    data = _rand(12, 10, 2, seed=6)
    # RTIF path → RasterReader
    flat = str(tmp_path / "flat.rtif")
    info = ImageInfo(12, 10, 2, data.dtype)
    rio.create(flat, info)
    rio.write_strip(flat, info, whole(12, 10), data)
    s = as_source(flat)
    assert isinstance(s, RasterReader)
    assert s.capabilities() == {CAP_RANGE_READABLE}
    np.testing.assert_array_equal(s.read_region(), data)
    # RTIC path → TiledSource (magic sniff, not extension)
    tiled = str(tmp_path / "tiled.bin")
    _write_rtic(tiled, data, tile_rows=8)
    t = as_source(tiled)
    assert isinstance(t, TiledSource)
    assert t.capabilities() == {CAP_TILED, CAP_PYRAMIDAL, CAP_RANGE_READABLE}
    np.testing.assert_array_equal(t.read_region(), data)
    t.close()
    # ndarray → ArraySource; Source passthrough; everything else rejects
    a = as_source(data)
    assert isinstance(a, ArraySource)
    scene = SyntheticScene(4, 4)
    assert as_source(scene) is scene
    with pytest.raises(TypeError):
        as_source(42)


def test_as_sink_dispatch(tmp_path):
    t = as_sink(str(tmp_path / "o.rtic"))
    assert isinstance(t, TileWriter)
    f = as_sink(str(tmp_path / "o.rtif"))
    assert isinstance(f, ParallelRasterWriter)
    assert as_sink(t) is t
    with pytest.raises(TypeError):
        as_sink(42)


def test_read_write_many(tmp_path):
    data = _rand(24, 16, 2, seed=8)
    info = ImageInfo(24, 16, 2, data.dtype)
    regions = StripeSplitter(n_splits=4).split(whole(24, 16), info)
    path = str(tmp_path / "many.rtif")
    w = ParallelRasterWriter(path)
    w.begin(info)
    w.write_many([(r, data[r.slices()]) for r in regions], n_writers=3)
    w.end()
    reader = RasterReader(path)
    blocks = reader.read_many(regions, n_readers=3)
    for r, b in zip(regions, blocks):
        np.testing.assert_array_equal(b, data[r.slices()])
    np.testing.assert_array_equal(reader.read_region(), data)


def test_deprecated_wrappers_delegate(tmp_path):
    data = _rand(12, 8, 2, seed=7)
    info = ImageInfo(12, 8, 2, data.dtype)
    strips = [
        (r, data[r.slices()])
        for r in StripeSplitter(n_splits=3).split(whole(12, 8), info)
    ]
    path = str(tmp_path / "dep.rtif")
    with pytest.warns(DeprecationWarning):
        rio.parallel_write(path, info, strips, n_writers=2)
    with pytest.warns(DeprecationWarning):
        got = rio.read_region(path)
    np.testing.assert_array_equal(got, data)
    with pytest.warns(DeprecationWarning):
        blocks = rio.parallel_read(path, [r for r, _ in strips], n_readers=2)
    for (r, b), g in zip(strips, blocks):
        np.testing.assert_array_equal(g, b)


# -- pipeline integration -----------------------------------------------------

def test_run_pipeline_sink_writes_tiled(tmp_path):
    out = str(tmp_path / "p6.rtic")
    src = SyntheticScene(40, 24, bands=3, dtype=np.float32, seed=1)
    res, mapper = PP.run_pipeline(
        "P6", src, sink=out, splitter=StripeSplitter(n_splits=4)
    )
    assert isinstance(mapper, TileWriter)
    p, m = PP.p6_conversion(src)
    oracle = np.asarray(p.pull(m, p.info(m).full_region))
    back = as_source(out)
    assert isinstance(back, TiledSource)
    try:
        np.testing.assert_array_equal(back.read_region(), oracle)
        # the written pyramid serves zooms bit-equal to decimating the output
        np.testing.assert_array_equal(
            back.overview(1).read_region(), oracle[::2, ::2]
        )
    finally:
        back.close()


def test_run_pipeline_sink_flat_and_errors(tmp_path):
    out = str(tmp_path / "io.rtif")
    src = SyntheticScene(16, 12, bands=2, dtype=np.float32)
    PP.run_pipeline("IO", src, sink=out, splitter=StripeSplitter(n_splits=2))
    np.testing.assert_array_equal(
        RasterReader(out).read_region(),
        np.asarray(src.generate(whole(16, 12))),
    )
    with pytest.raises(ValueError):
        PP.run_pipeline("IO", src, sink=out, mapper_factory=lambda: None)
    pair = PP.io_passthrough(src)
    with pytest.raises(ValueError):
        PP.run_pipeline(pair, sink=out)


def test_tiled_source_feeds_pipeline(tmp_path):
    """TiledSource is a first-class pipeline source: streaming over it
    equals the eager pull, and the streaming engine's read-ahead hook
    fires (region schedule handed to the source before the loop)."""
    path = str(tmp_path / "feed.rtic")
    data = _rand(48, 32, 4, seed=11)
    _write_rtic(path, data, tile_rows=16)
    oracle_src = TiledSource(path)
    try:
        p, m = PP.p6_conversion(oracle_src)
        oracle = np.asarray(p.pull(m, p.info(m).full_region))
    finally:
        oracle_src.close()
    src = TiledSource(path)  # fresh container: nothing cached yet
    try:
        p2, m2 = PP.p6_conversion(src)
        StreamingExecutor(p2, m2, StripeSplitter(n_splits=4)).run()
        np.testing.assert_array_equal(np.asarray(m2.result), oracle)
        assert src.stats()["readahead_scheduled"] > 0
    finally:
        src.close()


# -- catalog layer (P8/P9) ----------------------------------------------------

def test_mosaic_later_scene_wins():
    a = ArraySource(np.full((4, 4, 1), 1.0, np.float32))
    b = ArraySource(np.full((4, 4, 1), 2.0, np.float32))
    cat = SceneCatalog([
        SceneEntry(a, ImageRegion((0, 0), (4, 4))),
        SceneEntry(b, ImageRegion((2, 2), (4, 4))),
    ])
    src = MosaicSource(cat)
    img = np.asarray(src.generate(src.output_info().full_region))
    assert img.shape == (6, 6, 1)  # union bounding box
    assert img[0, 0, 0] == 1.0
    assert img[3, 3, 0] == 2.0  # overlap: catalog order, later wins
    assert img[5, 0, 0] == 0.0  # uncovered canvas: fill value
    # windowed reads reassemble identically (region independence)
    win = ImageRegion((1, 1), (3, 4))
    np.testing.assert_array_equal(
        np.asarray(src.generate(win)), img[win.slices()]
    )
    assert len(cat.select(ImageRegion((0, 0), (2, 2)))) == 1
    assert len(cat.select(ImageRegion((2, 2), (2, 2)))) == 2


def test_scene_entry_validates_dims():
    a = ArraySource(np.zeros((4, 4, 1), np.float32))
    with pytest.raises(ValueError):
        SceneEntry(a, ImageRegion((0, 0), (5, 4)))


def test_p9_accepts_catalog_and_explicit_scenes():
    from repro.raster import demo_time_series

    cat = demo_time_series(24, 16, periods=2, seed=3)
    p1, m1 = PP.p9_ndvi_composite(cat)
    r1 = np.asarray(p1.pull(m1, p1.info(m1).full_region))
    p2, m2 = PP.p9_ndvi_composite(*[e.source for e in cat.by_time()])
    r2 = np.asarray(p2.pull(m2, p2.info(m2).full_region))
    np.testing.assert_array_equal(r1, r2)
    assert r1.shape == (24, 16, 1)


# -- cross-executor: tiled reads hit the shared plan registry -----------------
CODE_TILED_SPMD = r"""
import os, tempfile
import numpy as np
from repro import pipelines as PP
from repro.core import ImageInfo, PlanCache, StreamingExecutor, StripeSplitter
from repro.core.parallel import ParallelExecutor
from repro.raster import SyntheticScene, TiledSource, TileWriter

tmp = tempfile.mkdtemp()
path = os.path.join(tmp, "scene.rtic")
scene = SyntheticScene(48, 32, bands=4, dtype=np.float32)
info = scene.output_info()
data = np.asarray(scene.generate(info.full_region))
w = TileWriter(path, tile_rows=16, levels=2)
w.begin(info)
w.consume(info.full_region, data)
w.end()

src = TiledSource(path)
p, m = PP.p2_textures(src, radius=2, levels=4)

cache = PlanCache()
StreamingExecutor(p, m, StripeSplitter(n_splits=4), plan_cache=cache,
                  prefetch=0).run()
streamed = np.array(m.result)
# the streaming engine handed its region schedule to the source BEFORE the
# region loop (fresh container: nothing was cached yet)
assert src.stats()["readahead_scheduled"] > 0, src.stats()
oracle = np.asarray(p.pull(m, p.info(m).full_region))
np.testing.assert_array_equal(streamed, oracle)
lowers0, compiles0 = cache.stats.lowers, cache.stats.compiles

# SPMD on the matching strip geometry: pure registry hits — the tiled
# read_record is part of the signature, so the hit is exact, not aliased
pe = ParallelExecutor(p, m, plan_cache=cache)
pe.run()
assert pe.plan.unified, "fell off the unified strip path"
assert cache.stats.lowers == lowers0, cache.stats
assert cache.stats.compiles == compiles0, cache.stats
np.testing.assert_array_equal(np.asarray(m.result), streamed)
src.close()
print("TILED_SPMD_OK")
"""


def test_tiled_streamed_then_spmd_zero_new_lowers(subproc):
    out = subproc(CODE_TILED_SPMD, devices=4, timeout=1800)
    assert "TILED_SPMD_OK" in out
