"""The benchmark's own spans around the program's source and sink.

``Spans`` records host intervals on ``time.perf_counter`` and, while the
profiler runs, as ``jax.profiler.TraceAnnotation`` events named
``chipbench.<kind>``, so the trace reduction can put them on the device
clock.  ``TimedSource`` wraps the stored scene's ``TiledSource.generate``
(kind ``read``); ``TimedSink`` wraps ``TileWriter.consume`` and ``end``
(kind ``write``) and records when each region's pixels are committed.
"""
from __future__ import annotations

import os
import threading
import time
from typing import List, Tuple

import jax

from repro.raster.tiled import TiledSource, TileWriter


class Spans:
    def __init__(self):
        self._lock = threading.Lock()
        self.items: List[Tuple[str, float, float]] = []

    def span(self, kind: str):
        return _Span(self, kind)

    def add(self, kind: str, t0: float, t1: float) -> None:
        with self._lock:
            self.items.append((kind, t0, t1))

    def of(self, kind: str) -> List[Tuple[float, float]]:
        with self._lock:
            return [(a, b) for k, a, b in self.items if k == kind]


class _Span:
    def __init__(self, spans: Spans, kind: str):
        self.spans, self.kind = spans, kind

    def __enter__(self):
        self._ann = jax.profiler.TraceAnnotation(f"chipbench.{self.kind}")
        self._ann.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        t1 = time.perf_counter()
        self._ann.__exit__(*exc)
        self.spans.add(self.kind, self.t0, t1)
        return False


class TimedSource(TiledSource):
    """A stored scene read through the program's tiled source, timed."""

    def __init__(self, path: str, spans: Spans):
        super().__init__(path)
        self._spans = spans

    def generate(self, out_region):
        with self._spans.span("read"):
            return super().generate(out_region)


class TimedSink(TileWriter):
    """The program's RTIC writer; records commit times, pixels and the
    bytes of each finished file.

    The product file lives in memory (``memfd_create``), reopened by
    ``TileWriter.begin`` through ``/proc/self/fd`` and truncated on every
    pass: the window measures the host's write work (tile scatter, the
    overview pyramid, copies, system calls) but not the disk's writeback,
    which is throttled differently on every machine, and a run writes no
    product to disk at all."""

    def __init__(self, spans: Spans, tile: int):
        self._memfd = os.memfd_create("chipbench-product")
        super().__init__(f"/proc/self/fd/{self._memfd}", tile_rows=tile)
        self._spans = spans
        #: (perf_counter s, pixels) of each committed region
        self.commits: List[Tuple[float, int]] = []
        self.bytes_written = 0

    def consume(self, out_region, data) -> None:
        with self._spans.span("write"):
            super().consume(out_region, data)
        self.commits.append((time.perf_counter(), out_region.num_pixels))

    def end(self) -> None:
        with self._spans.span("write"):
            super().end()
        self.bytes_written += os.fstat(self._memfd).st_size

    def close(self) -> None:
        """Release the in-memory file."""
        os.close(self._memfd)
