"""Program spans (``repro.core.tracing``) of the streaming executor and the
tiled writer, read back from the profiler's trace: every span is there, on
the thread the contract names, with each strip's origin, and the bytes on
the ``d2h``, ``consume`` and ``flush`` spans add up to what moved."""
import collections
import os

import jax
import numpy as np
import pytest

from repro import pipelines as PP
from repro.core import ImageInfo, StreamingExecutor, StripeSplitter, whole
from repro.raster import TiledSource, TileWriter

ROWS, COLS, BANDS, STRIP = 44, 30, 3, 8  # a ragged last strip
DISPATCH = ("begin", "wait_inputs", "dispatch", "d2h", "wait_write", "end")
PER_STRIP = ("describe", "wait_inputs", "dispatch", "d2h", "wait_write", "consume")


def _stored_scene(path):
    data = np.random.default_rng(0).integers(0, 4096, (ROWS, COLS, BANDS), np.uint16)
    w = TileWriter(str(path), tile_rows=16)
    w.begin(ImageInfo(ROWS, COLS, BANDS, np.uint16))
    w.consume(whole(ROWS, COLS), data)
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
    assert names == set(DISPATCH) | {"describe", "consume", "flush"}
    origins = collections.defaultdict(set)
    for n, _, st in spans:
        if "row0" in st:
            origins[n].add((st["row0"], st["col0"]))
    want = {(r.row0, r.col0) for r in strips}
    for n in PER_STRIP:
        assert origins[n] == want, n

    # the dispatching thread's spans share one line; with prefetch the
    # describe pass and the writes run on lines of their own
    lines = collections.defaultdict(set)
    for n, line, _ in spans:
        lines[n].add(line)
    (dispatch,) = set().union(*(lines[n] for n in DISPATCH))
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
    assert total["consume"] > 0 and total["flush"] > 0
