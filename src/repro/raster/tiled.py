"""RTIC: the tiled, pyramidal, range-readable raster container (COG-style).

Cloud-native geospatial serving reads *byte ranges* of one immutable object:
a fixed header, internally tiled pixel data, and stored overview levels, so
any window at any zoom costs a handful of range requests — never a whole-file
download.  RTIC reproduces that layout over the same data model as RTIF:

    bytes [0, 4096)      header: magic + JSON metadata (dims, dtype, geo,
                         tile geometry, level count, footer index location)
    bytes [4096, ...)    tile blobs: raw row-major pixel-interleaved samples,
                         one contiguous blob per (level, ty, tx) tile; edge
                         tiles are stored clipped (ragged right/bottom)
    footer               JSON index: per-level dims + tile → (offset, length)

Overview level ``L`` stores the ``2**L``-decimated image — level pixel
``(r, c)`` equals full-resolution pixel ``(r * 2**L, c * 2**L)``, exactly the
:class:`~repro.raster.sources.DecimatedSource` contract, so serving a zoom
from a stored level or from an on-the-fly decimation is bit-identical.

Access goes through a minimal **range-read abstraction** (``read(offset,
length)``): :class:`FileRangeReader` serves a local file via ``os.pread``;
:class:`MemoryRangeReader` serves an in-memory blob and counts every request
— the test/bench stand-in for a remote object store.  :class:`TiledSource`
assembles windows from cached tiles and prefetches scheduled tiles on a
background thread (``read_ahead`` — the streaming engine hands it the region
schedule, overlapping range fetches with compute).  :class:`TileWriter` is
the matching sink: ``begin()`` fixes every tile's byte range at every level,
each consumed region is written straight to those ranges (its level-0 rows
and its decimated overview pixels, no staging tiles), and ``end()`` writes
the footer index and seals the header — ``TileWriter`` output is exactly
what ``TiledSource`` ingests (round-trip and byte-for-byte oracle tests in
``tests/test_tiled_io.py``).
"""
from __future__ import annotations

import json
import os
import queue
import threading
from collections import OrderedDict
from typing import Dict, Iterable, List, Optional, Tuple

import jax.numpy as jnp
import numpy as np

from repro.core.process_object import GeoTransform, ImageInfo, Mapper, Source
from repro.core.region import ImageRegion, tile_cover, whole
from repro.core.tracing import span
from repro.raster.protocol import (
    CAP_PYRAMIDAL,
    CAP_RANGE_READABLE,
    CAP_TILED,
    RasterSink,
    RasterSource,
)

TILED_MAGIC = b"RTIC0001"
TILED_HEADER_BYTES = 4096

#: default internal tile geometry (COG-ish; small enough for the test scenes)
DEFAULT_TILE = 64


# -- the range-read abstraction ---------------------------------------------


class FileRangeReader:
    """Range reads on a local file (``os.pread`` — positional, thread-safe).

    The 'local object store': every access is an explicit (offset, length)
    request, the access pattern a remote store would see."""

    def __init__(self, path: str):
        self.path = path
        self._fd: Optional[int] = os.open(path, os.O_RDONLY)
        self._lock = threading.Lock()
        self.requests = 0
        self.bytes_read = 0

    def size(self) -> int:
        return os.fstat(self._fd).st_size

    def read(self, offset: int, length: int) -> bytes:
        buf = os.pread(self._fd, length, offset)
        with self._lock:
            self.requests += 1
            self.bytes_read += len(buf)
        return buf

    def close(self) -> None:
        if self._fd is not None:
            os.close(self._fd)
            self._fd = None

    def stats(self) -> Dict[str, int]:
        return {"requests": self.requests, "bytes_read": self.bytes_read}


class MemoryRangeReader:
    """Range reads over an in-memory blob — the remote-object-store stand-in.

    Serves slices of one immutable ``bytes`` object and counts every request,
    so tests and benches can assert *how many* range requests a window or an
    overview costs without any network in the loop.  ``latency_s`` adds a
    fixed per-request sleep to model round-trip time (read-ahead overlap
    becomes measurable)."""

    def __init__(self, blob: bytes, latency_s: float = 0.0):
        self._blob = blob
        self.latency_s = float(latency_s)
        self._lock = threading.Lock()
        self.requests = 0
        self.bytes_read = 0

    @classmethod
    def from_file(cls, path: str, latency_s: float = 0.0) -> "MemoryRangeReader":
        with open(path, "rb") as f:
            return cls(f.read(), latency_s=latency_s)

    def size(self) -> int:
        return len(self._blob)

    def read(self, offset: int, length: int) -> bytes:
        if self.latency_s > 0.0:
            import time

            time.sleep(self.latency_s)
        buf = self._blob[offset : offset + length]
        with self._lock:
            self.requests += 1
            self.bytes_read += len(buf)
        return buf

    def close(self) -> None:
        pass

    def stats(self) -> Dict[str, int]:
        return {"requests": self.requests, "bytes_read": self.bytes_read}


# -- the shared container (one per open file, shared by overview views) ------


def _level_dims(rows: int, cols: int, level: int) -> Tuple[int, int]:
    f = 1 << level
    return -(-rows // f), -(-cols // f)


class _TiledContainer:
    """Parsed RTIC file + tile LRU cache + background read-ahead thread.

    One container is shared by every :class:`TiledSource` view of the file
    (all overview levels), so cache and prefetcher are per-file, not
    per-view.  Tile fetches are idempotent (the blob is immutable), so the
    cache is a plain lock-guarded LRU: a rare duplicate fetch between the
    prefetch thread and a synchronous read costs one extra range request,
    never wrong pixels."""

    def __init__(self, reader, cache_tiles: int = 256, owns_reader: bool = True):
        self.reader = reader
        self.owns_reader = owns_reader
        head = reader.read(0, TILED_HEADER_BYTES)
        if not head.startswith(TILED_MAGIC):
            raise ValueError("not an RTIC container")
        meta = json.loads(head[len(TILED_MAGIC):].rstrip(b"\0").decode())
        self.rows = int(meta["rows"])
        self.cols = int(meta["cols"])
        self.bands = int(meta["bands"])
        self.dtype = np.dtype(meta["dtype"])
        self.geo = GeoTransform(*meta["geo"])
        self.nodata = meta["nodata"]
        self.tile_rows = int(meta["tile_rows"])
        self.tile_cols = int(meta["tile_cols"])
        index = json.loads(
            reader.read(meta["index_offset"], meta["index_length"]).decode()
        )
        #: per level: {"rows", "cols", "tiles": {"ty,tx": [offset, length]}}
        self.levels: List[dict] = index["levels"]
        self._lock = threading.Lock()
        self._cache: "OrderedDict[Tuple[int, int, int], np.ndarray]" = OrderedDict()
        self._cache_tiles = max(1, int(cache_tiles))
        self.tile_hits = 0
        self.tile_misses = 0
        self.readahead_scheduled = 0
        self._queue: "queue.Queue[Optional[Tuple[int, int, int]]]" = queue.Queue()
        self._worker: Optional[threading.Thread] = None
        self._closed = False

    @property
    def n_levels(self) -> int:
        return len(self.levels)

    def level_info(self, level: int) -> ImageInfo:
        lv = self.levels[level]
        f = 1 << level
        geo = GeoTransform(
            self.geo.origin_x,
            self.geo.origin_y,
            self.geo.spacing_x * f,
            self.geo.spacing_y * f,
        )
        return ImageInfo(
            lv["rows"], lv["cols"], self.bands, self.dtype, geo, self.nodata
        )

    def _tile_region(self, level: int, ty: int, tx: int) -> ImageRegion:
        lv = self.levels[level]
        tile = ImageRegion(
            (ty * self.tile_rows, tx * self.tile_cols),
            (self.tile_rows, self.tile_cols),
        )
        return tile.clamp(whole(lv["rows"], lv["cols"]))

    def tile(self, level: int, ty: int, tx: int) -> np.ndarray:
        key = (level, ty, tx)
        with self._lock:
            hit = self._cache.get(key)
            if hit is not None:
                self._cache.move_to_end(key)
                self.tile_hits += 1
                return hit
            self.tile_misses += 1
        offset, length = self.levels[level]["tiles"][f"{ty},{tx}"]
        raw = self.reader.read(offset, length)
        region = self._tile_region(level, ty, tx)
        arr = np.frombuffer(raw, dtype=self.dtype).reshape(
            region.rows, region.cols, self.bands
        )
        with self._lock:
            self._cache[key] = arr
            self._cache.move_to_end(key)
            while len(self._cache) > self._cache_tiles:
                self._cache.popitem(last=False)
        return arr

    def read_region(self, level: int, region: ImageRegion) -> np.ndarray:
        lv = self.levels[level]
        full = whole(lv["rows"], lv["cols"])
        if not full.contains(region):
            raise ValueError(
                f"read_region {region} outside level-{level} image {full}"
            )
        out = np.empty(
            (region.rows, region.cols, self.bands), dtype=self.dtype
        )
        for ty, tx, tile in tile_cover(
            region, self.tile_rows, self.tile_cols, bounds=full
        ):
            ov = tile.intersect(region)
            data = self.tile(level, ty, tx)
            out[ov.relative_to(region).slices()] = data[
                ov.relative_to(tile).slices()
            ]
        return out

    # -- async read-ahead ----------------------------------------------------
    def _worker_loop(self) -> None:
        while True:
            key = self._queue.get()
            if key is None:
                return
            try:
                self.tile(*key)
            except Exception:
                # prefetch is best-effort; the synchronous read path raises
                # the real error when (if) the tile is actually needed
                pass

    def schedule(self, keys: Iterable[Tuple[int, int, int]]) -> int:
        """Enqueue tile fetches on the background thread (started lazily)."""
        n = 0
        with self._lock:
            if self._closed:
                return 0
            if self._worker is None:
                self._worker = threading.Thread(
                    target=self._worker_loop, daemon=True, name="rtic-readahead"
                )
                self._worker.start()
            fresh = [k for k in keys if k not in self._cache]
            self.readahead_scheduled += len(fresh)
            n = len(fresh)
        for k in fresh:
            self._queue.put(k)
        return n

    def drain(self, timeout: float = 5.0) -> None:
        """Block until the prefetch queue is empty (tests/benches only)."""
        import time

        deadline = time.monotonic() + timeout
        while not self._queue.empty() and time.monotonic() < deadline:
            time.sleep(0.001)

    def close(self) -> None:
        with self._lock:
            if self._closed:
                return
            self._closed = True
            worker = self._worker
        if worker is not None:
            self._queue.put(None)
            worker.join(timeout=5.0)
        if self.owns_reader:
            self.reader.close()

    def stats(self) -> Dict[str, int]:
        out = {
            "tile_hits": self.tile_hits,
            "tile_misses": self.tile_misses,
            "readahead_scheduled": self.readahead_scheduled,
            "cached_tiles": len(self._cache),
        }
        if hasattr(self.reader, "stats"):
            out.update(self.reader.stats())
        return out


# -- the source --------------------------------------------------------------


class TiledSource(Source, RasterSource):
    """Reads one level of an RTIC container through the range-read backend.

    ``source`` is a file path (opened with :class:`FileRangeReader`) or any
    range reader (``read(offset, length)`` — e.g. :class:`MemoryRangeReader`
    for the remote stand-in).  Pixels are a pure function of absolute
    coordinates (the container is immutable), so the source is
    region-independent and runs on every executor; ``read_record`` stamps the
    tile geometry + level into plan signatures so a re-tiled container never
    aliases a flat source's plan.
    """

    def __init__(
        self,
        source,
        level: int = 0,
        cache_tiles: int = 256,
        name: Optional[str] = None,
    ):
        if isinstance(source, _TiledContainer):
            self._c = source
        elif isinstance(source, (str, os.PathLike)):
            self._c = _TiledContainer(
                FileRangeReader(os.fspath(source)), cache_tiles=cache_tiles
            )
        else:  # a range reader
            self._c = _TiledContainer(
                source, cache_tiles=cache_tiles, owns_reader=False
            )
        if not (0 <= level < self._c.n_levels):
            raise ValueError(
                f"level {level} not stored (container has {self._c.n_levels})"
            )
        self._level = int(level)
        super().__init__(name or f"tiled:L{self._level}")

    def capabilities(self) -> frozenset:
        return frozenset({CAP_TILED, CAP_PYRAMIDAL, CAP_RANGE_READABLE})

    def output_info(self) -> ImageInfo:
        return self._c.level_info(self._level)

    def generate(self, out_region: ImageRegion) -> jnp.ndarray:
        return jnp.asarray(self._c.read_region(self._level, out_region))

    def read_region(self, region: Optional[ImageRegion] = None) -> np.ndarray:
        if region is None:
            region = self.output_info().full_region
        return self._c.read_region(self._level, region)

    def read_record(self):
        return ("tiled", self._c.tile_rows, self._c.tile_cols, self._level)

    def overview(self, level: int) -> Source:
        """Stored pyramid levels; past the deepest stored level, decimate it."""
        if level <= 0:
            return self
        target = self._level + int(level)
        deepest = self._c.n_levels - 1
        if target <= deepest:
            return TiledSource(self._c, level=target)
        base = TiledSource(self._c, level=deepest)
        from repro.raster.sources import DecimatedSource

        return DecimatedSource(base, 2 ** (target - deepest))

    def read_ahead(self, regions: Iterable[ImageRegion]) -> int:
        info = self.output_info()
        full = info.full_region
        keys: List[Tuple[int, int, int]] = []
        seen = set()
        for region in regions:
            for ty, tx, _ in tile_cover(
                region.clamp(full), self._c.tile_rows, self._c.tile_cols,
                bounds=full,
            ):
                key = (self._level, ty, tx)
                if key not in seen:
                    seen.add(key)
                    keys.append(key)
        return self._c.schedule(keys)

    def stats(self) -> Dict[str, int]:
        return self._c.stats()

    def close(self) -> None:
        self._c.close()


# -- the sink ----------------------------------------------------------------

#: most buffers one ``os.pwritev`` call takes
_IOV_MAX = os.sysconf("SC_IOV_MAX")
#: columns interleaved at a time: a block's rows of one band stay in cache
_INTERLEAVE_COLS = 256


def _pwritev(fd: int, bufs: list, offset: int) -> int:
    """Write ``bufs`` back to back from ``offset``; returns the calls made."""
    calls = 0
    while bufs:
        batch = bufs[:_IOV_MAX]
        n = os.pwritev(fd, batch, offset)
        calls += 1
        offset += n
        if n == sum(map(len, batch)):
            bufs = bufs[len(batch):]
            continue
        if n == 0:
            raise OSError(f"pwritev wrote nothing at offset {offset}")
        # a short write: go on from the first byte not written
        i = 0
        while n >= len(batch[i]):
            n -= len(batch[i])
            i += 1
        bufs = [batch[i][n:]] + bufs[i + 1:]
    return calls


class TileWriter(Mapper, RasterSink):
    """Writes consumed regions into a fresh RTIC container.

    ``begin`` fixes every tile's byte range, level by level in row-major
    tile order, and sizes the file to the end of the tile area.  Each
    consumed region is then written straight to its final ranges: the rows
    of its level-0 pixels, and its decimated pixels at every overview level
    (gathered one whole pixel at a time), from views of the region's data
    (data whose rows are not C-ordered, or in another dtype, is first
    interleaved into a buffer the consuming thread keeps; the streaming
    executor's strips come with C-ordered rows).
    Regions need not align with the tile grid; any disjoint cover, in any
    order and from several threads at once, gives the same file, and pixels
    no region covered read as zero.  ``end`` writes the footer index after
    the tile area and seals the header.  ``levels`` counts total pyramid
    levels including full resolution; the default adds levels until the
    coarsest fits in one tile (capped at 9).
    """

    thread_safe = True  # disjoint regions write disjoint byte ranges

    def __init__(
        self,
        path: str,
        tile_rows: int = DEFAULT_TILE,
        tile_cols: Optional[int] = None,
        levels: Optional[int] = None,
        name: Optional[str] = None,
    ):
        super().__init__(name or f"tilewrite:{path}")
        self.path = path
        self.tile_rows = int(tile_rows)
        self.tile_cols = int(tile_cols if tile_cols is not None else tile_rows)
        if self.tile_rows < 1 or self.tile_cols < 1:
            raise ValueError("tile size must be >= 1")
        self._levels_arg = levels
        self._fd: Optional[int] = None

    def capabilities(self) -> frozenset:
        return frozenset({CAP_TILED, CAP_PYRAMIDAL})

    def begin(self, info: ImageInfo) -> None:
        self._info = info
        if self._levels_arg is not None:
            n_levels = max(1, int(self._levels_arg))
        else:
            n_levels = 1
            while (
                n_levels < 9
                and max(_level_dims(info.rows, info.cols, n_levels - 1))
                > max(self.tile_rows, self.tile_cols)
            ):
                n_levels += 1
        self._dims = [
            _level_dims(info.rows, info.cols, lv) for lv in range(n_levels)
        ]
        self._dtype = np.dtype(info.dtype)
        #: one pixel, all bands, as a single element
        self._pixel = np.dtype((np.void, info.bands * self._dtype.itemsize))
        #: per level: {"ty,tx": [offset, length]}, fixed from here on
        self._index: List[Dict[str, List[int]]] = []
        offset = TILED_HEADER_BYTES
        for r, c in self._dims:
            tiles = {}
            for ty, tx, tile in tile_cover(
                whole(r, c), self.tile_rows, self.tile_cols, bounds=whole(r, c)
            ):
                length = tile.num_pixels * self._pixel.itemsize
                tiles[f"{ty},{tx}"] = [offset, length]
                offset += length
            self._index.append(tiles)
        self._index_offset = offset
        self._fd = os.open(
            self.path, os.O_RDWR | os.O_CREAT | os.O_TRUNC, 0o644
        )
        # the header and any tile no region covers stay holes: zeros
        os.ftruncate(self._fd, offset)
        #: each consuming thread's interleave buffer
        self._local = threading.local()

    def consume(self, out_region: ImageRegion, data: np.ndarray) -> None:
        with span("consume", row0=out_region.row0, col0=out_region.col0) as sp:
            written, ranges, interleaved = self._consume(out_region, data)
            sp.set_metadata(bytes=written, ranges=ranges, interleaved=interleaved)

    def _consume(
        self, out_region: ImageRegion, data: np.ndarray
    ) -> Tuple[int, int, int]:
        """Write one region's pixels at every level; returns the bytes and
        the byte ranges written, and the bytes interleaved first."""
        full = self._info.full_region
        if not full.contains(out_region):
            raise ValueError(f"consume {out_region} outside image {full}")
        pix, interleaved = self._pixels(out_region, data)
        region = out_region
        written = ranges = 0
        for lv in range(len(self._dims)):
            if lv:
                # level L keeps the full-resolution pixels at multiples of
                # 2**L (the DecimatedSource sampling grid): the even rows
                # and columns of level L - 1
                r_start, c_start = region.row0 % 2, region.col0 % 2
                pix = np.ascontiguousarray(pix[r_start::2, c_start::2])
                region = ImageRegion(
                    ((region.row0 + r_start) // 2, (region.col0 + c_start) // 2),
                    pix.shape,
                )
            if region.is_empty():
                break
            n, k = self._write_level(lv, region, pix)
            written += n
            ranges += k
        return written, ranges, interleaved

    def _pixels(
        self, region: ImageRegion, data: np.ndarray
    ) -> Tuple[np.ndarray, int]:
        """The region's pixels as a ``(rows, cols)`` array of whole pixels,
        each row C-ordered (rows may lie further apart, as in a strip the
        plan entry hands back), and the bytes interleaved to make it: none
        for such a region in the file's dtype.  Data in another layout (a
        rank-3 array pulled from a TPU comes rows fastest) or dtype is
        interleaved a block of columns and one band at a time, which keeps
        numpy's strided copy in cache, into a buffer the calling thread
        keeps, so its pages are touched once and not for every region."""
        arr = np.asarray(data).reshape(region.rows, region.cols, self._info.bands)
        interleaved = 0
        rows_apart = arr.strides[0] >= arr[0].nbytes  # in order, disjoint
        if (arr.dtype != self._dtype or not arr[0].flags.c_contiguous
                or not rows_apart):
            buf = getattr(self._local, "buf", None)
            if buf is None or buf.size < arr.size:
                buf = self._local.buf = np.empty(arr.size, self._dtype)
            out = buf[: arr.size].reshape(arr.shape)
            for c0 in range(0, region.cols, _INTERLEAVE_COLS):
                cols = slice(c0, c0 + _INTERLEAVE_COLS)
                for b in range(arr.shape[2]):
                    out[:, cols, b] = arr[:, cols, b]
            arr = out
            interleaved = arr.nbytes
        return arr.view(self._pixel).reshape(region.rows, region.cols), interleaved

    def _write_level(
        self, lv: int, region: ImageRegion, pix: np.ndarray
    ) -> Tuple[int, int]:
        """Write ``pix``, the pixels of ``region`` of level ``lv``, into that
        level's tiles; returns the bytes and the byte ranges written."""
        px = self._pixel.itemsize
        stride = pix.strides[0]
        # the bytes from the first pixel to the last, rows ``stride`` apart
        src = memoryview(np.lib.stride_tricks.as_strided(
            pix.view(np.uint8), ((region.rows - 1) * stride + region.cols * px,),
            (1,), writeable=False,
        ))
        tiles = self._index[lv]
        written = ranges = 0
        for ty, tx, tile in tile_cover(
            region, self.tile_rows, self.tile_cols,
            bounds=whole(*self._dims[lv]),
        ):
            ov = tile.intersect(region)
            width = ov.cols * px
            first = (
                (ov.row0 - region.row0) * stride + (ov.col0 - region.col0) * px
            )
            rows = [
                src[s : s + width]
                for s in range(first, first + ov.rows * stride, stride)
            ]
            dst = tiles[f"{ty},{tx}"][0] + (
                (ov.row0 - tile.row0) * tile.cols + ov.col0 - tile.col0
            ) * px
            if ov.cols == tile.cols:  # whole tile rows: one range
                ranges += _pwritev(self._fd, rows, dst)
            else:
                for i, row in enumerate(rows):
                    ranges += _pwritev(self._fd, [row], dst + i * tile.cols * px)
            written += ov.rows * width
        return written, ranges

    def end(self) -> None:
        if self._fd is None:
            return
        with span("flush") as sp:
            sp.set_metadata(bytes=self._flush())

    def _flush(self) -> int:
        """Seal the file: the index after the tile area, then the header;
        returns the bytes written."""
        info = self._info
        index_payload = json.dumps(
            {
                "levels": [
                    {"rows": r, "cols": c, "tiles": self._index[lv]}
                    for lv, (r, c) in enumerate(self._dims)
                ]
            }
        ).encode()
        _pwritev(self._fd, [index_payload], self._index_offset)
        meta = {
            "rows": info.rows,
            "cols": info.cols,
            "bands": info.bands,
            "dtype": self._dtype.str,
            "geo": [
                info.geo.origin_x,
                info.geo.origin_y,
                info.geo.spacing_x,
                info.geo.spacing_y,
            ],
            "nodata": info.nodata,
            "tile_rows": self.tile_rows,
            "tile_cols": self.tile_cols,
            "levels": len(self._dims),
            "index_offset": self._index_offset,
            "index_length": len(index_payload),
        }
        head = TILED_MAGIC + json.dumps(meta).encode()
        if len(head) > TILED_HEADER_BYTES:
            raise ValueError("RTIC header overflow")
        _pwritev(self._fd, [head.ljust(TILED_HEADER_BYTES, b"\0")], 0)
        os.close(self._fd)
        self._fd = None
        self._local = None
        return len(index_payload) + TILED_HEADER_BYTES
