"""Program spans (``repro.core.tracing``) of the streaming executor and the
tiled writer, read back from the profiler's trace: every span is there, on
the thread the contract names, with each strip's origin, and the bytes on
the ``d2h``, ``consume`` and ``flush`` spans add up to what moved; each
``consume`` counts the byte ranges it wrote and the bytes it interleaved."""
import collections
import json
import os

import jax
import numpy as np
import pytest

from repro import pipelines as PP
from repro.core import (
    ImageInfo, PlanCache, StreamingExecutor, StripeSplitter, run_pool, whole,
)
from repro.raster import SyntheticScene, TiledSource, TileWriter
from repro.raster.tiled import TILED_HEADER_BYTES, TILED_MAGIC

ROWS, COLS, BANDS, STRIP = 44, 30, 3, 8  # a ragged last strip
DISPATCH = ("begin", "wait_inputs", "dispatch", "d2h", "wait_write", "end")
PER_STRIP = ("describe", "read", "wait_inputs", "dispatch", "d2h", "wait_write", "consume")


def _stored_scene(path, rows=ROWS, cols=COLS, bands=BANDS, seed=0):
    data = np.random.default_rng(seed).integers(0, 4096, (rows, cols, bands), np.uint16)
    w = TileWriter(str(path), tile_rows=16)
    w.begin(ImageInfo(rows, cols, bands, np.uint16))
    w.consume(whole(rows, cols), data)
    w.end()


def _program_spans(trace_dir):
    """(name, line index, stats) of each ``repro.*`` event on a host plane."""
    (path,) = list(trace_dir.glob("plugins/profile/*/*.xplane.pb"))
    out = []
    for plane in jax.profiler.ProfileData.from_file(str(path)).planes:
        if not plane.name.startswith("/host:"):
            continue
        for i, line in enumerate(plane.lines):
            for ev in line.events:
                if ev.name.startswith("repro."):
                    out.append((ev.name[len("repro."):], (plane.name, i), dict(ev.stats)))
    return out


@pytest.mark.parametrize("prefetch", [2, 0])
def test_streamed_pass_emits_every_span(tmp_path, prefetch):
    _stored_scene(tmp_path / "scene.rtic")
    product = tmp_path / "product.rtic"
    src = TiledSource(str(tmp_path / "scene.rtic"))
    try:
        p, m = PP.p6_conversion(src, mapper_factory=lambda: TileWriter(str(product), tile_rows=16))
        ex = StreamingExecutor(p, m, StripeSplitter(stripe_rows=STRIP), prefetch=prefetch)
        strips = ex.my_regions()
        assert len(strips) == -(-ROWS // STRIP)
        with jax.profiler.trace(str(tmp_path / "trace")):
            ex.run()
    finally:
        src.close()
    spans = _program_spans(tmp_path / "trace")

    names = {n for n, _, _ in spans}
    assert names == set(DISPATCH) | {"describe", "read", "consume", "flush"}
    origins = collections.defaultdict(set)
    for n, _, st in spans:
        if "row0" in st:
            origins[n].add((st["row0"], st["col0"]))
    want = {(r.row0, r.col0) for r in strips}
    for n in PER_STRIP:
        assert origins[n] == want, n

    # the dispatching thread's spans share one line; with prefetch the
    # describe pass, the reads and the writes run on lines of their own
    lines = collections.defaultdict(set)
    for n, line, _ in spans:
        lines[n].add(line)
    (dispatch,) = set().union(*(lines[n] for n in DISPATCH))
    assert lines["read"] == lines["describe"]
    if prefetch:
        assert dispatch not in lines["describe"] | lines["consume"]
    else:
        assert lines["describe"] == lines["consume"] == {dispatch}
    assert lines["flush"] == {dispatch}  # ``end`` runs where it is called

    # bytes: the output crossed once; the writer's spans wrote the file
    total = collections.Counter()
    for n, _, st in spans:
        total[n] += st.get("bytes", 0)
    assert total["d2h"] == ROWS * COLS * BANDS * np.dtype(np.uint8).itemsize
    assert total["consume"] + total["flush"] == os.path.getsize(product)
    assert total["consume"] > 0
    # each region went straight to its byte ranges; ``end`` wrote only the
    # index and the header
    assert all(st["ranges"] > 0 for n, _, st in spans if n == "consume")
    with open(product, "rb") as f:
        head = f.read(TILED_HEADER_BYTES)
    meta = json.loads(head[len(TILED_MAGIC):].rstrip(b"\0"))
    assert total["flush"] == meta["index_length"] + TILED_HEADER_BYTES


def test_each_pool_worker_is_a_dispatching_thread(tmp_path):
    """``run_pool`` runs the same region loop on each worker thread: with
    two workers, ``dispatch``, ``d2h`` and ``wait_write`` come from two
    threads, each with the origins of the strips that worker ran, the two
    together every strip once; ``begin`` and ``end`` frame the pass once,
    on the calling thread."""
    p, m = PP.p6_conversion(SyntheticScene(ROWS, COLS, bands=BANDS, dtype=np.float32))
    splitter = StripeSplitter(stripe_rows=STRIP)
    info = p.info(m)
    strips = splitter.split(info.full_region, info)
    with jax.profiler.trace(str(tmp_path / "trace")):
        run_pool(p, m, splitter, n_workers=2, scheduler="static")
    spans = _program_spans(tmp_path / "trace")

    count = collections.Counter(n for n, _, _ in spans)
    assert count["begin"] == count["end"] == 1
    workers = collections.defaultdict(lambda: collections.defaultdict(list))
    for n, line, st in spans:
        if n in ("dispatch", "d2h", "wait_write"):
            workers[line][n].append((st["row0"], st["col0"]))
    assert len(workers) == 2
    for names in workers.values():
        assert names["dispatch"] and names["dispatch"] == names["d2h"] == names["wait_write"]
    ran = sorted(o for names in workers.values() for o in names["dispatch"])
    assert ran == sorted((r.row0, r.col0) for r in strips)
    (frame,) = {line for n, line, _ in spans if n in ("begin", "end")}
    assert frame not in workers


def _described_bytes(p, m, region):
    """Bytes of the windows the describe pass asks of each input for
    ``region``: what the strip's read stage must deliver."""
    desc = p.describe_pull(m, region, virtual=p.virtual_describe_mode())
    wins = desc.windows or (None,) * len(desc.reads)
    total = 0
    for (src, _, req), w in zip(desc.reads, wins):
        info = p.info(src)
        rows, cols = w or (req.rows, req.cols)
        total += rows * cols * info.bands * np.dtype(info.dtype).itemsize
    return total


def _stored_job(tmp_path, job, mapper_factory=None):
    """P3 on two stored files (XS and PAN, two grids) or P6 on one: the
    stored sources and the built (pipeline, mapper) pair."""
    if job == "P3":
        _stored_scene(tmp_path / "xs.rtic", 10, 13, 4, seed=1)
        _stored_scene(tmp_path / "pan.rtic", 40, 52, 1, seed=2)
        srcs = [TiledSource(str(tmp_path / n)) for n in ("xs.rtic", "pan.rtic")]
        return srcs, PP.p3_pansharpening(*srcs, use_pallas=False,
                                         mapper_factory=mapper_factory)
    _stored_scene(tmp_path / "scene.rtic")
    srcs = [TiledSource(str(tmp_path / "scene.rtic"))]
    return srcs, PP.p6_conversion(*srcs, mapper_factory=mapper_factory)


@pytest.mark.parametrize("job", ["P3", "P6"])
def test_read_spans_count_each_strip_inputs(tmp_path, job):
    """``read`` carries one strip's inputs: two arrays for P3 (XS and PAN,
    two stored files on two grids), one for P6; its bytes add up to the
    described windows' bytes over the strips.  For P3 that is more than the
    stored rasters hold: the XS rows (and PAN halo rows) a strip's footprint
    shares with its neighbours are read again at every seam."""
    srcs, (p, m) = _stored_job(tmp_path, job)
    try:
        ex = StreamingExecutor(p, m, StripeSplitter(stripe_rows=STRIP))
        strips = ex.my_regions()
        with jax.profiler.trace(str(tmp_path / "trace")):
            ex.run()
    finally:
        for s in srcs:
            s.close()
    reads = [st for n, _, st in _program_spans(tmp_path / "trace") if n == "read"]
    assert sorted((st["row0"], st["col0"]) for st in reads) == sorted(
        (r.row0, r.col0) for r in strips)
    assert {st["inputs"] for st in reads} == {len(srcs)}
    want = sum(_described_bytes(p, m, r) for r in strips)
    assert sum(st["bytes"] for st in reads) == want
    stored = sum(s.info().rows * s.info().cols * s.info().bands * 2 for s in srcs)
    if job == "P3":
        assert want > stored


def _write(path, info, regions):
    w = TileWriter(str(path), tile_rows=16)
    w.begin(info)
    for region, data in regions:
        w.consume(region, data)
    w.end()
    with open(path, "rb") as f:
        return f.read()


@pytest.mark.parametrize("job", ["P3", "P6"])
def test_streamed_strips_reach_the_writer_c_ordered(tmp_path, job):
    """Streamed into a ``TileWriter``, each strip comes from the plan
    entry's host call with C-ordered rows in the file's dtype, so no
    ``consume`` interleaves (``interleaved`` 0), and the file is byte for
    byte the one the canonical program's strips give; for P6, an exact
    conversion, also the eager oracle's.  On the CPU every array is row-major, so this guards
    the contract, not a TPU's layout: ``chip_smoke.py`` checks that on the
    chip."""
    product = tmp_path / "product.rtic"
    srcs, (p, m) = _stored_job(tmp_path, job, lambda: TileWriter(str(product), tile_rows=16))
    try:
        ex = StreamingExecutor(p, m, StripeSplitter(stripe_rows=STRIP))
        strips = ex.my_regions()
        with jax.profiler.trace(str(tmp_path / "trace")):
            ex.run()
        got = product.read_bytes()
        info = p.info(m)
        cache = PlanCache()
        device_strips = []
        for region in strips:
            desc = p.describe_pull(m, region, virtual=p.virtual_describe_mode())
            entry = cache.compiled_for(desc, lambda: p.lower_pull(desc))
            out, _ = jax.jit(entry.canonical_fn)(
                desc.read_sources(), desc.initial_pstates(), desc.origins()
            )
            assert out.shape == region.size + (info.bands,)
            device_strips.append((region, np.asarray(out)))
        assert got == _write(tmp_path / "device.rtic", info, device_strips)
        if job == "P6":
            eager = np.asarray(p.pull(m, info.full_region))
            assert got == _write(tmp_path / "eager.rtic", info, [(info.full_region, eager)])
    finally:
        for s in srcs:
            s.close()
    consumes = [st for n, _, st in _program_spans(tmp_path / "trace") if n == "consume"]
    assert len(consumes) == len(strips)
    assert all(st["bytes"] > 0 and st["interleaved"] == 0 for st in consumes)


@pytest.mark.parametrize("order", ["C", "F"])
def test_consume_counts_the_bytes_it_interleaved(tmp_path, order):
    """A region handed to ``TileWriter.consume`` columns-major, as a rank-3
    strip pulled from a TPU comes, is interleaved on the host: the span's
    ``interleaved`` is its level-0 bytes; a C-ordered one is written from
    views (``interleaved`` 0).  Both give the same file."""
    data = np.random.default_rng(3).integers(0, 255, (ROWS, COLS, BANDS), np.uint8)
    info = ImageInfo(ROWS, COLS, BANDS, np.uint8)
    region = whole(ROWS, COLS)
    with jax.profiler.trace(str(tmp_path / "trace")):
        got = _write(tmp_path / "product.rtic", info, [(region, np.asarray(data, order=order))])
    assert got == _write(tmp_path / "c.rtic", info, [(region, data)])
    (st,) = [st for n, _, st in _program_spans(tmp_path / "trace") if n == "consume"]
    assert st["interleaved"] == (data.nbytes if order == "F" else 0)
    assert st["bytes"] > data.nbytes  # level 0 and the overviews
