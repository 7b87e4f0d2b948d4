"""The compiled-plan cache + async double-buffered streaming engine.

Covers the three engine layers: canonical plans share one compiled function
per (shape, boundary, plan-key) signature; prefetch/write-behind is
bit-identical to the synchronous loop; persistent filters run through the
compiled path with state bit-identical to the eager oracle; the
work-stealing pool drains every region exactly once; and every driver of
the shared region loop matches the whole-image pull.
"""
import threading

import numpy as np
import pytest

from repro import pipelines as PP
from repro.core import (
    Filter,
    Pipeline,
    PlanCache,
    StreamingExecutor,
    StripeSplitter,
    TileSplitter,
    WorkStealingQueue,
    execute,
    run_pool,
)
from repro.filters import BandStatistics, gaussian_smoothing
from repro.raster import MemoryMapper, SyntheticScene, make_spot6_pair


def _src(rows=48, cols=32, bands=3):
    return SyntheticScene(rows, cols, bands=bands, dtype=np.float32)


def _stats_pipeline(rows=40, cols=30):
    p = Pipeline()
    s = p.add(SyntheticScene(rows, cols, bands=3, dtype=np.float32))
    st = p.add(BandStatistics(bands=3), [s])
    m = p.add(MemoryMapper(), [st])
    return p, m


# -- layer 1+2: canonical plans + PlanCache ---------------------------------
def test_uniform_stripes_compile_exactly_once():
    """A halo-free pipeline over uniform stripes: one trace, N−1 hits."""
    p, m = PP.p6_conversion(_src(48, 32))
    cache = PlanCache()
    res = StreamingExecutor(
        p, m, StripeSplitter(n_splits=8), plan_cache=cache, prefetch=0
    ).run()
    assert res.cache_stats is cache.stats
    assert cache.stats.compiles == 1
    assert cache.stats.misses == 1
    assert cache.stats.hits == 7


def test_halo_pipeline_compiles_once_despite_boundaries():
    """Halo pipelines describe against the virtual padded geometry, so border
    stripes (whose halo reads spill past the image rows) land on the interior
    signature: ONE compile for the whole striped run, the spill materialized
    by edge replication at the read stage."""
    p = Pipeline()
    s = p.add(_src(60, 24))
    g = p.add(gaussian_smoothing(1.0), [s])
    m = p.add(MemoryMapper(), [g])
    cache = PlanCache()
    StreamingExecutor(
        p, m, StripeSplitter(n_splits=10), plan_cache=cache, prefetch=0
    ).run()
    assert cache.stats.compiles == 1
    assert cache.stats.hits == 9


def test_stacked_stencils_keep_exact_border_describes():
    """A halo landing on a row-stencil INTERMEDIATE (gauss → sobel) refuses
    virtual describes: the eager oracle edge-replicates the gaussian's output
    rows at the image border, which virtual geometry (replicating only raw
    source rows) cannot reproduce.  The run then pays per-border signatures
    but stays bit-compatible with the whole-image pull.  Halos that reach a
    source — directly or through row-transparent pointwise filters — keep
    the one-signature virtual path."""
    from repro.filters import BandMath, MeanShift, SobelGradient

    p = Pipeline()
    s = p.add(_src(48, 40))
    g = p.add(gaussian_smoothing(1.2), [s])
    e = p.add(SobelGradient(), [g])
    m = p.add(MemoryMapper(), [e])
    assert not p.virtual_rows_safe()
    assert not StreamingExecutor(p, m).describe_virtual
    cache = PlanCache()
    StreamingExecutor(
        p, m, StripeSplitter(n_splits=6), plan_cache=cache, prefetch=0
    ).run()
    assert cache.stats.compiles == 3  # top / interior / bottom

    # single stencil onto a source: virtual stays on
    p2 = Pipeline()
    s2 = p2.add(_src(48, 40))
    g2 = p2.add(gaussian_smoothing(1.2), [s2])
    m2 = p2.add(MemoryMapper(), [g2])
    assert p2.virtual_rows_safe()

    # stencil onto a row-transparent pointwise run onto a source: still safe
    p3 = Pipeline()
    s3 = p3.add(_src(48, 40))
    b3 = p3.add(BandMath(lambda x: x * 0.5 + 1.0, out_bands=3), [s3])
    f3 = p3.add(MeanShift(hs=2, hr=60.0, n_iter=1), [b3])
    m3 = p3.add(MemoryMapper(), [f3])
    assert p3.virtual_rows_safe()


def test_plan_cache_shared_across_executors():
    """Worker ranks sharing one cache compile once between them."""
    cache = PlanCache()
    for w in range(3):
        p, m = PP.p6_conversion(_src(48, 32))
        StreamingExecutor(
            p, m, StripeSplitter(n_splits=6), worker=w, n_workers=3,
            plan_cache=cache, prefetch=0,
        ).run()
    # node ids differ per pipeline instance, so each rank's pipeline gets its
    # own entry — but within a rank all uniform stripes share one
    assert cache.stats.compiles == 3


def test_plan_cache_lru_eviction():
    p, m = PP.p6_conversion(_src(10, 16))
    cache = PlanCache(max_entries=1)
    # 10 rows / 4 splits → three 3-row stripes + one 1-row stripe
    StreamingExecutor(
        p, m, StripeSplitter(n_splits=4), plan_cache=cache, prefetch=0
    ).run()
    assert cache.stats.compiles == 2
    assert cache.stats.evictions == 1
    assert len(cache) == 1


def test_p3_registered_in_pipeline_registry():
    assert PP.ALL["P3"] is PP.p3_pansharpening
    assert set(PP.ALL) >= {"P1", "P2", "P3", "P4", "P5", "P6", "P7", "IO"}


# -- layer 3: async double buffering ----------------------------------------
P17_CASES = {
    "P1": lambda: PP.p1_orthorectification(_src(40, 32, bands=4)),
    "P2": lambda: PP.p2_textures(_src(40, 32, bands=4)),
    "P3": lambda: PP.p3_pansharpening(*make_spot6_pair(10, 8)),
    "P4": lambda: PP.p4_classification(_src(40, 32, bands=4)),
    "P5": lambda: PP.p5_meanshift(_src(40, 32, bands=4), hs=2, n_iter=2),
    "P6": lambda: PP.p6_conversion(_src(40, 32, bands=4)),
    "P7": lambda: PP.p7_resampling(_src(20, 16, bands=4)),
}


@pytest.mark.parametrize("name", list(P17_CASES))
def test_prefetch_bit_identical_to_sync(name):
    """Overlapping reads/writes must not change a single bit of output."""
    build = P17_CASES[name]
    p1, m1 = build()
    sync = StreamingExecutor(p1, m1, StripeSplitter(n_splits=5), prefetch=0).run()
    p2, m2 = build()
    asyn = StreamingExecutor(p2, m2, StripeSplitter(n_splits=5), prefetch=3).run()
    np.testing.assert_array_equal(m1.result, m2.result)
    assert sync.regions_processed == asyn.regions_processed
    assert sync.pixels_processed == asyn.pixels_processed


def test_prefetch_keep_outputs_ordered():
    p, m = PP.p6_conversion(_src(48, 32))
    res = execute(p, m, StripeSplitter(n_splits=6), keep_outputs=True, prefetch=2)
    assert res.outputs is not None and len(res.outputs) == 6
    np.testing.assert_array_equal(np.concatenate(res.outputs, axis=0), m.result)


def test_execute_separates_ctor_and_run_kwargs():
    p, m = PP.p6_conversion(_src(24, 16))
    res = execute(p, m, keep_outputs=True, prefetch=0, scheduler="lpt")
    assert res.outputs is not None
    assert res.regions_processed == len(res.outputs)


# -- persistent filters through the compiled path ---------------------------
def test_persistent_compiled_state_bit_identical_to_eager():
    p1, m1 = _stats_pipeline()
    compiled = StreamingExecutor(p1, m1, StripeSplitter(n_splits=7), prefetch=2).run()
    p2, m2 = _stats_pipeline()
    eager = StreamingExecutor(p2, m2, StripeSplitter(n_splits=7), use_jit=False).run()
    assert compiled.cache_stats is not None  # really took the compiled path
    assert compiled.cache_stats.compiles >= 1
    a = compiled.persistent_results["BandStatistics"]
    b = eager.persistent_results["BandStatistics"]
    assert set(a) == set(b)
    for k in a:
        np.testing.assert_array_equal(np.asarray(a[k]), np.asarray(b[k]), err_msg=k)
    np.testing.assert_array_equal(m1.result, m2.result)


def test_persistent_compiled_tiles_match_global_stats():
    p, m = _stats_pipeline(36, 30)
    res = StreamingExecutor(p, m, TileSplitter(10, 13), prefetch=2).run()
    img = np.asarray(m.result)
    stats = res.persistent_results["BandStatistics"]
    np.testing.assert_allclose(
        np.asarray(stats["mean"]), img.reshape(-1, 3).mean(0), rtol=1e-4
    )
    np.testing.assert_allclose(
        np.asarray(stats["max"]), img.reshape(-1, 3).max(0), rtol=1e-5
    )


def test_region_dependent_persistent_filter_via_plan_key():
    """accumulate()'s region argument is canonical (shape-only) under the
    compiled path; a filter whose state depends on absolute coordinates must
    override plan_key — then compiled matches eager exactly."""
    import jax.numpy as jnp

    from repro.core import PersistentFilter, Reduction

    class RowWeighted(PersistentFilter):
        state_reductions = {"acc": Reduction("sum")}

        def plan_key(self, out_region):
            return out_region.index  # absolute coords enter the trace

        def reset(self):
            return {"acc": jnp.zeros((), jnp.float32)}

        def accumulate(self, st, region, x, mask=None):
            return {"acc": st["acc"] + region.row0 * x.sum()}

    def mk():
        p = Pipeline()
        s = p.add(SyntheticScene(32, 16, bands=1, dtype=np.float32))
        f = p.add(RowWeighted(), [s])
        m = p.add(MemoryMapper(), [f])
        return p, m

    p1, m1 = mk()
    compiled = StreamingExecutor(p1, m1, StripeSplitter(n_splits=8)).run()
    p2, m2 = mk()
    eager = StreamingExecutor(p2, m2, StripeSplitter(n_splits=8), use_jit=False).run()
    np.testing.assert_array_equal(
        np.asarray(compiled.persistent_results["RowWeighted"]["acc"]),
        np.asarray(eager.persistent_results["RowWeighted"]["acc"]),
    )
    # the plan_key forces one compile per distinct origin
    assert compiled.cache_stats.compiles == 8


def test_mapper_end_called_on_error():
    """A failing region must not leak the writer: end() runs on the error
    path (releasing StripWriter descriptors) before the exception surfaces."""
    from repro.core.process_object import Mapper

    class Boom(Mapper):
        def __init__(self):
            super().__init__()
            self.ended = 0

        def consume(self, region, data):
            raise RuntimeError("boom")

        def end(self):
            self.ended += 1

    p = Pipeline()
    s = p.add(_src(24, 16))
    m = p.add(Boom(), [s])
    with pytest.raises(RuntimeError):
        StreamingExecutor(p, m, StripeSplitter(n_splits=4), prefetch=2).run()
    assert m.ended == 1
    p = Pipeline()
    s = p.add(_src(24, 16))
    m = p.add(Boom(), [s])
    with pytest.raises(RuntimeError):
        run_pool(p, m, StripeSplitter(n_splits=4), n_workers=2)
    assert m.ended == 1


# -- the work-stealing pool --------------------------------------------------
def test_work_stealing_queue_drains_exactly_once_concurrently():
    q = WorkStealingQueue(200, 4, costs=list(np.linspace(1, 3, 200)))
    taken = [[] for _ in range(4)]

    def drain(w):
        while True:
            i = q.take(w)
            if i is None:
                return
            taken[w].append(i)

    threads = [threading.Thread(target=drain, args=(w,)) for w in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    flat = sorted(i for lst in taken for i in lst)
    assert flat == list(range(200))


def test_work_stealing_queue_steals_half_from_most_loaded():
    q = WorkStealingQueue(8, 2, costs=[10, 10, 10, 10, 1, 1, 1, 1])
    # worker 1 drains its own cheap half, then must steal worker 0's tail
    for _ in range(4):
        assert q.take(1) in (4, 5, 6, 7)
    # steal-half: one steal operation transfers the tail block [2, 3] in
    # original order — 2 comes back, 3 lands in the thief's deque
    stolen = q.take(1)
    assert stolen == 2
    assert q.steals == 1
    assert q.items_stolen == 2
    assert q.take(1) == 3  # from the thief's own deque, no second steal
    assert q.steals == 1


def test_work_stealing_steal_half_bounds_lock_traffic():
    """A lone thief draining a loaded victim: steal-half needs O(log n) steal
    operations (each a lock acquisition on the shared queue) where steal-one
    needed n — the contention bound that matters on very fine splits."""
    n = 64
    q = WorkStealingQueue(n, 2)  # worker 0 owns [0, 32), worker 1 owns [32, 64)
    taken = []
    while True:
        i = q.take(1)  # worker 1 does all the work; worker 0 never shows up
        if i is None:
            break
        taken.append(i)
    assert sorted(taken) == list(range(n))  # drained exactly once
    # 32 own items cost zero steals; the other 32 arrive in halving blocks:
    # 16, 8, 4, 2, 1, 1 → 6 steal operations, not 32
    assert q.steals <= 7
    assert q.items_stolen == 32


def test_run_pool_matches_oracle_and_compiles_once():
    p, m = PP.p6_conversion(_src(64, 32))
    res = run_pool(
        p, m, StripeSplitter(n_splits=16), n_workers=4, scheduler="work_stealing"
    )
    assert res.regions_processed == 16
    assert res.cache_stats.compiles == 1  # shared cache across all workers
    p2, m2 = PP.p6_conversion(_src(64, 32))
    whole = np.asarray(p2.pull(m2, p2.info(m2).full_region))
    np.testing.assert_array_equal(m.result, whole)


@pytest.mark.parametrize("scheduler", ["static", "lpt", "work_stealing"])
def test_run_pool_persistent_stats_any_scheduler(scheduler):
    p, m = _stats_pipeline(48, 30)
    res = run_pool(
        p, m, StripeSplitter(n_splits=12), n_workers=3, scheduler=scheduler
    )
    img = np.asarray(m.result)
    stats = res.persistent_results["BandStatistics"]
    # combine order differs per worker split → same tolerance as the seed's
    # split-invariance property test
    np.testing.assert_allclose(
        np.asarray(stats["mean"]), img.reshape(-1, 3).mean(0), rtol=1e-4
    )
    np.testing.assert_allclose(
        np.asarray(stats["max"]), img.reshape(-1, 3).max(0), rtol=1e-5
    )
    np.testing.assert_allclose(
        np.asarray(stats["std"]), img.reshape(-1, 3).std(0), rtol=1e-3, atol=1e-3
    )


def test_raster_writer_tile_split(tmp_path):
    """StripWriter's windowed pwrite path: tile splits (not full-width) land
    every pixel in its final in-file position."""
    from repro.raster import ParallelRasterWriter, RasterReader

    path = str(tmp_path / "tiles.rtif")
    p, m = PP.p6_conversion(
        _src(40, 28), mapper_factory=lambda: ParallelRasterWriter(path)
    )
    run_pool(p, m, TileSplitter(16, 12), n_workers=3, scheduler="work_stealing")
    p2, m2 = PP.p6_conversion(_src(40, 28))
    whole = np.asarray(p2.pull(m2, p2.info(m2).full_region))
    np.testing.assert_array_equal(RasterReader(path).read_region(), whole)


def test_run_pool_eager_path():
    p, m = _stats_pipeline(30, 20)
    res = run_pool(
        p, m, StripeSplitter(n_splits=6), n_workers=2, use_jit=False
    )
    assert res.cache_stats is None
    img = np.asarray(m.result)
    stats = res.persistent_results["BandStatistics"]
    np.testing.assert_allclose(
        np.asarray(stats["mean"]), img.reshape(-1, 3).mean(0), rtol=1e-4
    )


# -- the shared region loop, under every driver ------------------------------
REGION_LOOP_DRIVERS = {
    "streaming-prefetch0": lambda p, m, s, jit: StreamingExecutor(
        p, m, s, prefetch=0, use_jit=jit).run(keep_outputs=True),
    "streaming-prefetch2": lambda p, m, s, jit: StreamingExecutor(
        p, m, s, prefetch=2, use_jit=jit).run(keep_outputs=True),
    "pool-1": lambda p, m, s, jit: run_pool(
        p, m, s, n_workers=1, use_jit=jit, keep_outputs=True),
    "pool-3": lambda p, m, s, jit: run_pool(
        p, m, s, n_workers=3, use_jit=jit, keep_outputs=True),
}


@pytest.mark.parametrize("use_jit", [True, False], ids=["jit", "eager"])
@pytest.mark.parametrize("driver", list(REGION_LOOP_DRIVERS))
def test_region_loop_matches_whole_image_pull(driver, use_jit):
    """Whichever driver runs the region loop, and on the compiled or the
    eager step, a pipeline with a persistent filter gives the whole-image
    pull's pixels (in the sink and in the kept outputs, in region order)
    and its synthesized statistics."""
    p, m = _stats_pipeline(40, 30)
    res = REGION_LOOP_DRIVERS[driver](p, m, StripeSplitter(n_splits=6), use_jit)

    p2, m2 = _stats_pipeline(40, 30)
    (node,) = p2.persistent_nodes()
    state = {node.name: node.reset()}

    def hook(n, region, inputs):
        state[n.name] = n.accumulate(state[n.name], region, *inputs)

    whole = np.asarray(p2.pull(m2, p2.info(m2).full_region, persistent_hook=hook))
    want = node.synthesize(state[node.name])

    assert (res.cache_stats is not None) == use_jit
    assert res.regions_processed == 6
    assert res.pixels_processed == 40 * 30
    np.testing.assert_array_equal(m.result, whole)
    np.testing.assert_array_equal(np.concatenate(res.outputs, axis=0), whole)
    got = res.persistent_results[node.name]
    assert set(got) == set(want)
    # the regions' partial sums add up in another order; std is
    # sqrt(E[x²] - mean²), which cancels, so it keeps the tolerance of
    # test_run_pool_persistent_stats_any_scheduler
    for k in want:
        np.testing.assert_allclose(
            np.asarray(got[k]), np.asarray(want[k]),
            rtol=1e-3 if k == "std" else 1e-5, err_msg=k,
        )
