"""Plan-warm tile serving: grid geometry, admission control, signature
batching, and the warm-up protocol.

The load-bearing claims: (1) a batched (vmap) signature group is
**bit-identical** to per-tile streaming pulls — serving never changes
pixels; (2) after ``TileServer.warm`` the first live request performs zero
new lowers and zero new compiles (pure registry hits); (3) admission bounds
in-flight depth under a storm, shedding instead of queueing; (4) the
process-wide plan registry survives a serving-shaped concurrency storm
(many describe+hit threads racing a slow lower on another signature)
without duplicate compiles or deadlock.
"""
import threading
import time
from concurrent.futures import wait

import numpy as np
import pytest

from repro import pipelines as PP
from repro.core import (
    BatchedRegionPuller,
    ImageRegion,
    PlanCache,
    global_plan_cache,
)
from repro.raster import ArraySource, DecimatedSource, SyntheticScene
from repro.serve import AdmissionController, Shed, TileGrid, TileRequest, TileServer


# -- tile grid geometry ------------------------------------------------------
def test_tile_grid_regions_and_ragged_edges():
    g = TileGrid(rows=50, cols=70, tile_rows=16, tile_cols=32)
    assert (g.nx, g.ny) == (3, 4)
    assert g.region(0, 0) == ImageRegion((0, 0), (16, 32))
    # ragged last row/col clamp to the image
    assert g.region(2, 3) == ImageRegion((48, 64), (2, 6))
    assert sum(1 for _ in g.tiles()) == 12
    with pytest.raises(KeyError):
        g.region(3, 0)
    with pytest.raises(ValueError):
        TileGrid(0, 10, 4, 4)


def test_tile_grid_neighbors():
    g = TileGrid(rows=64, cols=64, tile_rows=16, tile_cols=16)
    assert set(g.neighbors(0, 0)) == {(0, 1), (1, 0), (1, 1)}
    assert len(g.neighbors(1, 1)) == 8
    assert (2, 2) not in g.neighbors(0, 0)


# -- decimated (zoom) sources ------------------------------------------------
def test_decimated_source_is_strided_view():
    rng = np.random.default_rng(0)
    base = ArraySource(rng.normal(size=(37, 29, 3)).astype(np.float32))
    dec = DecimatedSource(base, 4)
    info = dec.output_info()
    assert (info.rows, info.cols) == (10, 8)  # ceil(37/4), ceil(29/4)
    full = np.asarray(dec.generate(info.full_region))
    expect = np.asarray(base.array)[::4, ::4]
    np.testing.assert_array_equal(full, expect)
    # windowed read matches the same window of the full strided view,
    # including the ragged last tile
    win = ImageRegion((8, 4), (2, 4))
    np.testing.assert_array_equal(
        np.asarray(dec.generate(win)), expect[8:10, 4:8]
    )


def test_decimated_synthetic_scene_region_independent():
    base = SyntheticScene(64, 48, bands=2, dtype=np.float32)
    dec = DecimatedSource(base, 2)
    info = dec.output_info()
    full = np.asarray(dec.generate(info.full_region))
    tile = np.asarray(dec.generate(ImageRegion((8, 8), (16, 16))))
    np.testing.assert_array_equal(tile, full[8:24, 8:24])


# -- admission control -------------------------------------------------------
def test_admission_shed_policy_bounds_depth():
    ctl = AdmissionController(max_depth=2, policy="shed")
    assert ctl.try_admit() and ctl.try_admit()
    assert not ctl.try_admit()
    with pytest.raises(Shed):
        ctl.admit()
    ctl.release()
    assert ctl.try_admit()
    snap = ctl.snapshot()
    assert snap["admitted"] == 3 and snap["shed"] == 2
    assert snap["depth"] == 2 and snap["high_water"] == 2


def test_admission_block_policy_waits_for_release():
    ctl = AdmissionController(max_depth=1, policy="block", max_wait_s=5.0)
    ctl.admit()
    got = []

    def waiter():
        got.append(ctl.try_admit())

    t = threading.Thread(target=waiter)
    t.start()
    time.sleep(0.05)
    assert not got  # still blocked behind the full depth
    ctl.release()
    t.join(timeout=5)
    assert got == [True]
    # bounded wait: a second blocked admit times out into a shed
    assert not ctl.try_admit(timeout=0.05)
    assert ctl.snapshot()["shed"] == 1


def test_admission_release_must_pair_and_held_releases_on_error():
    ctl = AdmissionController(max_depth=4)
    with pytest.raises(RuntimeError):
        ctl.release()
    with pytest.raises(ValueError):
        with ctl.held():
            assert ctl.snapshot()["depth"] == 1
            raise ValueError("boom")
    assert ctl.snapshot()["depth"] == 0


# -- serving correctness: batched == per-tile streaming pulls ----------------
def _small_server(**kw):
    kw.setdefault("rows_xs", 32)
    kw.setdefault("cols_xs", 32)
    kw.setdefault("zooms", (0, 1))
    kw.setdefault("plan_cache", PlanCache())
    kw.setdefault("tile_cache_entries", 0)
    kw.setdefault("prefetch_neighbors", False)
    kw.setdefault("batch_sizes", (1, 4))
    return PP.build_tile_server(**kw)


def _all_requests(server):
    return [
        TileRequest(name, z, x, y)
        for name, z in server.entries()
        for x, y in server._entries[(name, z)].grid.tiles()
    ]


def test_batched_tiles_bit_identical_to_per_tile_pulls():
    """Every registered tile of P2/P3/P5 across two zooms, served through
    signature-batched vmap programs, must equal the unbatched per-tile pull
    bit for bit."""
    server = _small_server()
    reqs = _all_requests(server)
    tiles = server.serve(reqs)
    assert {r.pipeline for r in reqs} == {"P2", "P3", "P5"}
    for req, tile in zip(reqs, tiles):
        entry = server._entries[(req.pipeline, req.zoom)]
        region = entry.grid.region(req.x, req.y)
        oracle = entry.puller.pull_one(region)
        assert tile.shape == (region.rows, region.cols, tile.shape[-1])
        np.testing.assert_array_equal(np.asarray(tile), np.asarray(oracle))


def test_warm_then_first_requests_are_pure_registry_hits():
    server = _small_server(zooms=(0,))
    warm = server.warm()
    assert warm and all(w["signatures"] >= 1 for w in warm.values())
    before = server.plan_cache.stats_snapshot()
    server.serve(_all_requests(server))
    after = server.plan_cache.stats_snapshot()
    assert after["lowers"] == before["lowers"]
    assert after["compiles"] == before["compiles"]
    assert after["hits"] > before["hits"]


def test_serve_unknown_entry_and_bad_tile():
    server = _small_server(zooms=(0,), pipelines=("P2",))
    with pytest.raises(KeyError):
        server.serve_one(TileRequest("P9", 0, 0, 0))
    with pytest.raises(KeyError):
        server.serve_one(TileRequest("P2", 0, 99, 0))


def test_register_rejects_duplicates_and_persistent_pipelines():
    server = _small_server(zooms=(0,), pipelines=("P2",))
    scene = SyntheticScene(32, 32, bands=4)
    p, m = PP.p2_textures(scene)
    with pytest.raises(ValueError):
        server.register("P2", 0, p, m, 16)
    from repro.core import Pipeline
    from repro.filters import BandStatistics

    pp = Pipeline()
    s = pp.add(SyntheticScene(32, 32, bands=2, dtype=np.float32))
    st = pp.add(BandStatistics(bands=2), [s])
    from repro.raster import MemoryMapper

    mm = pp.add(MemoryMapper(), [st])
    with pytest.raises(ValueError):
        server.register("stats", 0, pp, mm, 16)


# -- the request engine: futures, batching, shed under storm -----------------
def test_submit_engine_batches_and_completes():
    server = _small_server(zooms=(0,), pipelines=("P2",))
    server.warm()
    with server:
        futs = [server.submit(r) for r in _all_requests(server)]
        done, not_done = wait(futs, timeout=60)
    assert not not_done
    for f in done:
        assert f.result().ndim == 3
    m = server.metrics()
    assert sum(k * v for k, v in m["batch_histogram"].items()) == len(futs)
    assert m["admission"]["depth"] == 0
    assert m["admission"]["admitted"] == m["admission"]["completed"]


def test_submit_sheds_beyond_admission_depth():
    server = _small_server(
        zooms=(0,),
        pipelines=("P5",),
        admission=AdmissionController(max_depth=2, policy="shed"),
        max_batch=2,
    )
    server.warm()
    reqs = _all_requests(server) * 8
    with server:
        futs = [server.submit(r) for r in reqs]
        wait(futs, timeout=60)
    shed = sum(1 for f in futs if isinstance(f.exception(), Shed))
    ok = sum(1 for f in futs if f.exception() is None)
    assert ok >= 2  # at least one batch got through
    assert shed >= 1  # the storm overran depth 2
    snap = server.admission.snapshot()
    assert snap["depth"] == 0 and snap["shed"] == shed
    assert snap["admitted"] == snap["completed"] == ok


def test_submit_requires_started_server_and_stop_is_idempotent():
    server = _small_server(zooms=(0,), pipelines=("P2",))
    with pytest.raises(RuntimeError):
        server.submit(TileRequest("P2", 0, 0, 0))
    server.start()
    with pytest.raises(RuntimeError):
        server.start()
    server.stop()
    server.stop()


def test_tile_cache_hit_skips_admission_and_prefetch_fills_neighbors():
    server = _small_server(
        zooms=(0,),
        pipelines=("P2",),
        tile_cache_entries=64,
        prefetch_neighbors=True,
    )
    server.warm()
    with server:  # neighbor prefetchers only run on a started server
        first = server.serve_one(TileRequest("P2", 0, 0, 0))
        admitted = server.admission.snapshot()["admitted"]
        again = server.serve_one(TileRequest("P2", 0, 0, 0))
        np.testing.assert_array_equal(np.asarray(first), np.asarray(again))
        assert server.admission.snapshot()["admitted"] == admitted  # cache hit
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline:
            server._drain_prefetched()
            if server.metrics()["prefetch"]["stored"] >= 1:
                break
            time.sleep(0.02)
    m = server.metrics()
    assert m["prefetch"]["enqueued"] >= 1
    assert m["prefetch"]["stored"] >= 1
    # a prefetched neighbor equals its served pull
    entry = server._entries[("P2", 0)]
    nreq = TileRequest("P2", 0, 1, 1)
    cached = server.tile_cache.get(nreq)
    if cached is not None:
        oracle = entry.puller.pull_one(entry.grid.region(1, 1))
        np.testing.assert_array_equal(np.asarray(cached), np.asarray(oracle))


# -- BatchedRegionPuller unit behavior ---------------------------------------
def test_batched_puller_bucket_rounding_and_oversize_chunking():
    scene = SyntheticScene(64, 16, bands=2, dtype=np.float32)
    p, m = PP.p6_conversion(scene)
    puller = BatchedRegionPuller(p, m, plan_cache=PlanCache(), batch_sizes=(1, 4))
    assert puller.bucket(1) == 1 and puller.bucket(3) == 4 and puller.bucket(4) == 4
    assert puller.bucket(9) == 4  # above the largest bucket: chunked by it
    regions = [ImageRegion((8 * i, 0), (8, 16)) for i in range(6)]
    tiles = puller.pull_many(regions)
    assert len(tiles) == 6
    for region, tile in zip(regions, tiles):
        np.testing.assert_array_equal(
            np.asarray(tile), np.asarray(puller.pull_one(region))
        )


def test_batched_puller_preserves_input_order_across_signatures():
    scene = SyntheticScene(50, 16, bands=2, dtype=np.float32)
    p, m = PP.p6_conversion(scene)
    puller = BatchedRegionPuller(p, m, plan_cache=PlanCache(), batch_sizes=(1, 4))
    # alternate two signature classes (10-row and 5-row tiles)
    regions = []
    for i in range(4):
        regions.append(ImageRegion((10 * i, 0), (10, 16)))
        regions.append(ImageRegion((40 + 5 * (i % 2), 0), (5, 16)))
    tiles = puller.pull_many(regions)
    for region, tile in zip(regions, tiles):
        assert tile.shape[0] == region.rows
        np.testing.assert_array_equal(
            np.asarray(tile), np.asarray(puller.pull_one(region))
        )


# -- the registry under a serving-shaped concurrency storm -------------------
def test_global_plan_cache_concurrent_serving_storm():
    """The serving workload shape on the process-wide registry: 16 threads
    describe + registry-hit + execute one warmed signature while another
    signature is being lowered slowly on a separate thread.  Exactly one
    counted lower per signature, exactly one XLA trace per signature (the
    entry priming lock), counters consistent, nobody deadlocks."""
    from repro.core.execplan import reset_global_plan_cache

    reset_global_plan_cache()
    try:
        cache = global_plan_cache()
        scene = SyntheticScene(64, 32, bands=2, dtype=np.float32)
        p, m = PP.p6_conversion(scene)
        desc_a = p.describe_pull(m, ImageRegion((0, 0), (16, 32)))
        desc_b = p.describe_pull(m, ImageRegion((32, 0), (8, 32)))
        assert desc_a.signature != desc_b.signature
        lower_calls = {"a": 0, "b": 0}
        lock = threading.Lock()
        barrier = threading.Barrier(17)
        errors = []

        def lower_a():
            with lock:
                lower_calls["a"] += 1
            return p.lower_pull(desc_a)

        def lower_b():
            with lock:
                lower_calls["b"] += 1
            time.sleep(0.2)  # a deliberately slow lower in flight
            return p.lower_pull(desc_b)

        # warm signature A the way TileServer.warm does: one lower, no trace
        # yet — the storm threads then race the FIRST execution too, which
        # the entry's priming lock must collapse to a single XLA trace.
        cache.compiled_for(desc_a, lower_a)

        def storm():
            try:
                barrier.wait(timeout=30)
                for i in range(40):
                    d = p.describe_pull(m, ImageRegion((16 * (i % 2), 0), (16, 32)))
                    entry = cache.compiled_for(d, lower_a)
                    if i % 10 == 0:  # exercise the compiled fn concurrently
                        entry.host(d.read_sources(), d.initial_pstates(), d.origins())
                    cache.stats_snapshot()
            except Exception as e:  # pragma: no cover — surfaced below
                errors.append(e)

        def slow_lowerer():
            try:
                barrier.wait(timeout=30)
                entry = cache.compiled_for(desc_b, lower_b)
                entry.host(desc_b.read_sources(), desc_b.initial_pstates(), desc_b.origins())
            except Exception as e:  # pragma: no cover
                errors.append(e)

        threads = [threading.Thread(target=storm) for _ in range(16)]
        threads.append(threading.Thread(target=slow_lowerer))
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
            assert not t.is_alive(), "registry deadlocked under serving storm"
        assert not errors
        assert lower_calls == {"a": 1, "b": 1}  # hits never re-lower
        snap = cache.stats_snapshot()
        assert snap["lowers"] == 2 and snap["misses"] == 2
        assert snap["hits"] == 16 * 40  # every storm lookup was a pure hit
        assert snap["compiles"] == 2  # one XLA trace per signature, no dupes
    finally:
        reset_global_plan_cache()
