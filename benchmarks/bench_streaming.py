"""Streaming-engine benchmark: plan-cache + double buffering.

Two measurements on a uniform-stripe P2 (Haralick textures) run:

  * engine_cached  — the PlanCache path (one compile per signature),
    synchronous (``prefetch=0``);
  * engine_async   — cached + double-buffered (``prefetch=2``), writing
    through the RTIF write-behind stage.

Reported ``derived`` columns: regions/sec for the engine rows, registry hits
for the compile row (one compile on striped P2: border stripes describe
against the virtual padded geometry, so every stripe shares the interior
signature), and sequential/pool wall-time ratio for the work-stealing
orchestrator row.

The ``plan_describe_vs_lower`` rows microbench the ExecutionPlan layer's
cache-hit cost: a registry hit runs the describe pass only, so its per-region
host overhead must beat the old hit path (describe **plus** rebuilding the
O(graph) closure tree).  ``run(quick=True)`` (CI smoke: ``--quick``) keeps
the cached-engine measurement and this microbench, and skips the slow
async/I/O/pool sweeps.
"""
from __future__ import annotations

import sys
import tempfile
import time
from pathlib import Path
from typing import List

import numpy as np

from repro import pipelines as PP
from repro.core import PlanCache, StreamingExecutor, StripeSplitter, run_pool
from repro.raster import ParallelRasterWriter, SyntheticScene

ROWS, COLS, STRIPES = 192, 64, 12


def _p2(tmp: Path, tag: str):
    src = SyntheticScene(ROWS, COLS, bands=4, dtype=np.float32)
    return PP.p2_textures(
        src, mapper_factory=lambda: ParallelRasterWriter(str(tmp / f"{tag}.rtif"))
    )


def _timed(executor: StreamingExecutor):
    t0 = time.perf_counter()
    res = executor.run()
    return time.perf_counter() - t0, res


def _plan_layer_microbench(out: List, quick: bool) -> None:
    """Cache-hit host cost: describe pass alone vs describe + closure build
    (what every registry hit used to pay before the describe/lower split).

    Uses a deep filter chain on a fine split — the regime the refactor
    targets (per-region host overhead scales with graph size) — and takes the
    best of several trials so scheduler noise doesn't drown the ratio."""
    from repro.filters import gaussian_smoothing
    from repro.raster import MemoryMapper

    from repro.core import Pipeline

    p = Pipeline()
    n = p.add(SyntheticScene(256, 64, bands=2, dtype=np.float32))
    for _ in range(12):
        n = p.add(gaussian_smoothing(1.0), [n])
    m = p.add(MemoryMapper(), [n])
    info = p.info(m)
    regions = StripeSplitter(n_splits=16).split(info.full_region, info)
    for r in regions:  # warm both walks
        p.describe_pull(m, r)
        p.compile_pull(m, r)

    reps, trials = (3, 3) if quick else (20, 5)

    def best(fn):
        times = []
        for _ in range(trials):
            t0 = time.perf_counter()
            for _ in range(reps):
                for r in regions:
                    fn(m, r)
            times.append((time.perf_counter() - t0) / (reps * len(regions)))
        return min(times)

    dt_describe = best(p.describe_pull)
    dt_lower = best(p.compile_pull)

    out.append(("plan_describe_pass_us", dt_describe * 1e6, dt_lower / dt_describe))
    out.append(("plan_describe_plus_lower_us", dt_lower * 1e6, dt_lower / dt_describe))
    if dt_describe >= dt_lower:
        print("# WARNING: describe pass not cheaper than describe+lower "
              f"({dt_describe*1e6:.1f}us vs {dt_lower*1e6:.1f}us)", file=sys.stderr)


def _tile_grid_bench(out: List, tmp: Path, quick: bool) -> None:
    """1-D strips vs the 2-D tile grid on a WIDE image (PR 9).

    An nr·nc-way strip split of a wide image yields long skinny stripes
    whose halo rows span the full width; the matching nr×nc tile grid
    (``padded_tile_grid``) keeps regions square-ish, so the halo perimeter
    per pixel shrinks.  Streaming over the Hr×Wc tile geometry is the
    single-process analogue of the 2-D SPMD mesh — and must still be ONE
    compile: grid-mode virtual describes give every tile (ragged columns
    included) the shared interior signature.  Derived columns: regions/sec
    for the strip row, strip/tile wall ratio for the tile row, registry
    hits for the compile row."""
    from repro.core import TileSplitter, padded_tile_grid

    grows, gcols = (64, 512) if quick else (96, 768)
    gnr, gnc = 2, 4

    def build(tag):
        src = SyntheticScene(grows, gcols, bands=4, dtype=np.float32)
        return PP.p2_textures(
            src,
            mapper_factory=lambda: ParallelRasterWriter(str(tmp / f"{tag}.rtif")),
        )

    p, m = build("grid_strips")
    dt_strips, res = _timed(
        StreamingExecutor(p, m, StripeSplitter(n_splits=gnr * gnc),
                          plan_cache=PlanCache(), prefetch=0)
    )
    out.append(("streaming_grid_strips_1d", dt_strips * 1e6,
                res.regions_processed / dt_strips))

    Hr, Wc, _, _ = padded_tile_grid(grows, gcols, gnr, gnc)
    p, m = build("grid_tiles")
    cache = PlanCache()
    dt_tiles, _ = _timed(
        StreamingExecutor(p, m, TileSplitter(Hr, Wc), plan_cache=cache,
                          prefetch=0)
    )
    out.append(("streaming_grid_tiles_2d", dt_tiles * 1e6, dt_strips / dt_tiles))
    out.append(("streaming_grid_tile_compiles", float(cache.stats.compiles),
                float(cache.stats.hits)))
    if cache.stats.compiles != 1:
        print(f"# WARNING: expected 1 compile on the P2 tile grid, got "
              f"{cache.stats.compiles}", file=sys.stderr)


def run(quick: bool = False) -> List:
    out = []
    with tempfile.TemporaryDirectory(prefix="bench_streaming_") as d:
        tmp = Path(d)
        splitter = StripeSplitter(n_splits=STRIPES)

        _plan_layer_microbench(out, quick)

        # compiled-plan cache, synchronous loop
        p, m = _p2(tmp, "cached")
        cache = PlanCache()
        dt_cached, res = _timed(
            StreamingExecutor(p, m, splitter, plan_cache=cache, prefetch=0)
        )
        regions = res.regions_processed
        out.append(("streaming_P2_engine_cached", dt_cached * 1e6,
                    regions / dt_cached))
        out.append(("streaming_P2_compiles", float(cache.stats.compiles),
                    float(cache.stats.hits)))
        if cache.stats.compiles != 1:  # virtual border describes: one signature
            print(f"# WARNING: expected 1 compile on striped P2, got "
                  f"{cache.stats.compiles}", file=sys.stderr)

        _tile_grid_bench(out, tmp, quick)
        if quick:
            return out

        # cached + async double buffering (measures read/write overlap)
        p, m = _p2(tmp, "async")
        dt_async, _ = _timed(
            StreamingExecutor(p, m, splitter, plan_cache=PlanCache(), prefetch=2)
        )
        out.append(("streaming_P2_engine_async", dt_async * 1e6, regions / dt_async))
        out.append(("streaming_P2_overlap", dt_async * 1e6, dt_cached / dt_async))

        # overlap on an I/O-bound pipeline (file → file copy): P2 above is
        # compute-bound, so double buffering shows its worth where the paper
        # says it matters — reads and writes hiding behind each other
        from repro.raster import RasterReader

        src_path = str(tmp / "io_src.rtif")
        p, m = PP.io_passthrough(
            SyntheticScene(2048, 512, bands=4, dtype=np.float32),
            mapper_factory=lambda: ParallelRasterWriter(src_path),
        )
        StreamingExecutor(p, m, StripeSplitter(n_splits=8)).run()
        io_splitter = StripeSplitter(n_splits=32)

        def _copy(tag, prefetch):
            p, m = PP.io_passthrough(
                RasterReader(src_path),
                mapper_factory=lambda: ParallelRasterWriter(str(tmp / f"{tag}.rtif")),
            )
            return _timed(
                StreamingExecutor(p, m, io_splitter, plan_cache=PlanCache(),
                                  prefetch=prefetch)
            )[0]

        dt_io_sync = _copy("io_sync", 0)
        dt_io_async = _copy("io_async", 4)
        out.append(("streaming_IO_overlap", dt_io_async * 1e6, dt_io_sync / dt_io_async))

        # orchestrator stage: sequential per-worker loop (seed) vs the
        # work-stealing thread pool on the same stage graph
        n_workers = 4
        stage_splitter = StripeSplitter(n_splits=n_workers * 4)

        p, m = _p2(tmp, "seq")
        t0 = time.perf_counter()
        for w in range(n_workers):  # the seed orchestrator's sequential loop
            StreamingExecutor(
                p, m, stage_splitter, worker=w, n_workers=n_workers
            ).run()
        dt_seq = time.perf_counter() - t0

        p, m = _p2(tmp, "pool")
        t0 = time.perf_counter()
        run_pool(p, m, stage_splitter, n_workers=n_workers, scheduler="work_stealing")
        dt_pool = time.perf_counter() - t0
        # no hard assert: on a loaded 1–2 core box the thread pool can lose to
        # the sequential loop; the derived ratio reports the outcome either way
        out.append(("orchestrator_ws_pool_vs_seq", dt_pool * 1e6, dt_seq / dt_pool))
    return out
