"""The unified ExecutionPlan layer: describe/lower split + shared registry.

Covers: describe-pass signatures are identical to the lowered plan's;
registry hits run zero lower passes (no closure rebuild); the process-wide
registry; cross-executor sharing — a pipeline streamed first is a registry
*hit* (zero new compiles, zero new lowers) for the thread pool; registry
counter consistency under concurrent races and LRU eviction.  The full
pipeline × executor equivalence matrix (streaming / pool / SPMD 2-4-8
devices vs the eager oracle) lives in tests/test_cross_executor_diff.py.
"""
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import pipelines as PP
from repro.core import (
    ImageRegion,
    Pipeline,
    PlanCache,
    StreamingExecutor,
    StripeSplitter,
    global_plan_cache,
    run_pool,
)
from repro.core.execplan import CacheStats, _CompiledEntry
from repro.filters import BandStatistics, gaussian_smoothing
from repro.raster import MemoryMapper, SyntheticScene, make_spot6_pair


def _graphs():
    p6, m6 = PP.p6_conversion(SyntheticScene(48, 32, bands=3, dtype=np.float32))
    p3, m3 = PP.p3_pansharpening(*make_spot6_pair(12, 8))
    halo = Pipeline()
    s = halo.add(SyntheticScene(60, 24, bands=2, dtype=np.float32))
    g = halo.add(gaussian_smoothing(1.0), [s])
    st = halo.add(BandStatistics(bands=2), [g])
    m = halo.add(MemoryMapper(), [st])
    return [(p6, m6), (p3, m3), (halo, m)]


# -- describe/lower split ----------------------------------------------------
def test_describe_signature_matches_compiled_plan():
    """The cheap describe pass and the full lower pass walk the same
    recursion: identical signature, reads, origins, persistent set."""
    for p, m in _graphs():
        for region in StripeSplitter(n_splits=5).split(
            p.info(m).full_region, p.info(m)
        ):
            desc = p.describe_pull(m, region)
            plan = p.compile_pull(m, region)
            assert desc.signature == plan.signature
            assert desc.origin_values == plan.origin_values
            assert desc.persistent_nodes == plan.persistent_nodes
            assert [(id(s), c, r) for s, c, r in desc.reads] == [
                (id(s), c, r) for s, c, r in plan.reads
            ]


def test_registry_hit_skips_lower_pass():
    """compiled_for runs the lower callback on misses only — a hit is
    describe-pass work plus a dict lookup, no closure tree."""
    p, m = PP.p6_conversion(SyntheticScene(40, 16, bands=2, dtype=np.float32))
    region = StripeSplitter(n_splits=4).split(p.info(m).full_region, p.info(m))[1]
    cache = PlanCache()
    calls = []

    def lower():
        calls.append(1)
        return p.lower_pull(desc)

    desc = p.describe_pull(m, region)
    e1 = cache.compiled_for(desc, lower)
    assert calls == [1] and cache.stats.lowers == 1 and cache.stats.misses == 1
    e2 = cache.compiled_for(desc, lower)
    assert e2 is e1
    assert calls == [1]  # hit: no second closure build
    assert cache.stats.hits == 1 and cache.stats.lowers == 1


def test_streaming_executor_lowers_once_per_signature():
    p, m = PP.p6_conversion(SyntheticScene(48, 32, bands=3, dtype=np.float32))
    cache = PlanCache()
    StreamingExecutor(
        p, m, StripeSplitter(n_splits=8), plan_cache=cache, prefetch=0
    ).run()
    assert cache.stats.lowers == cache.stats.compiles == 1
    assert cache.stats.hits == 7


@pytest.mark.parametrize("dtype", [np.uint8, np.float32])
@pytest.mark.parametrize("bands", [1, 4, 5])
def test_host_call_hands_back_c_ordered_pixels(bands, dtype):
    """The entry's host call gives the strip as a ``(rows, cols, bands)``
    host array equal to the canonical program's output, with equal
    persistent states, from one compiled program: a view of the lane-dense
    output, each row C-ordered and a whole number of 128-lane vectors long,
    so a sink writes it from views.  On the CPU every array is row-major,
    so this guards the contract, not a TPU's layout: ``chip_smoke.py``
    checks that on the chip."""
    rows, cols = 6, 7

    def canonical(arrays, pstates, origins):
        (x,) = arrays
        # band-planar blocks put back band-last, as a kernel's output is
        planar = jnp.stack([x[..., b] + origins[0] for b in range(bands)])
        out = jnp.moveaxis(planar, 0, -1).astype(dtype)
        return out, {"acc": pstates["acc"] + x.sum()}

    stats = CacheStats()
    entry = _CompiledEntry(canonical, stats)
    x = np.random.default_rng(bands).integers(0, 200, (rows, cols, bands))
    args = ([x.astype(np.float32)], {"acc": jnp.float32(1)}, (jnp.int32(3),))
    got, got_states = entry.host(*args)
    want, want_states = jax.jit(canonical)(*args)
    item = np.dtype(dtype).itemsize
    assert isinstance(got, np.ndarray) and got[0].flags.c_contiguous
    assert got.strides[1:] == (bands * item, item)
    assert got.strides[0] % (128 * item) == 0
    assert got.shape == want.shape == (rows, cols, bands)
    assert got.dtype == want.dtype == dtype
    np.testing.assert_array_equal(got, np.asarray(want))
    np.testing.assert_array_equal(got, (x + 3).astype(dtype))
    assert float(got_states["acc"]) == float(want_states["acc"]) == 1 + x.sum()
    assert stats.compiles == 1


def test_global_plan_cache_is_process_wide():
    assert global_plan_cache() is global_plan_cache()
    assert isinstance(global_plan_cache(), PlanCache)


def test_global_plan_cache_reset_preserves_old_counters():
    """reset_global_plan_cache swaps in a fresh registry but must never zero
    history out from under callers that captured the old one: a StreamResult
    holding the pre-reset ``cache_stats`` keeps its eviction/compile counters
    (the perf-trajectory CI snapshot reads them after the run)."""
    from repro.core.execplan import reset_global_plan_cache

    baseline = reset_global_plan_cache()  # isolate from other tests
    try:
        cache = global_plan_cache()
        assert cache is not baseline and len(cache) == 0
        # drive real evictions through a tiny bounded registry shim: fill the
        # GLOBAL cache via an executor, then overflow a bounded one sharing
        # the same stats object semantics
        p, m = PP.p6_conversion(SyntheticScene(24, 16, bands=2, dtype=np.float32))
        res = StreamingExecutor(
            p, m, StripeSplitter(n_splits=4), plan_cache=cache, prefetch=0
        ).run()
        assert res.cache_stats is cache.stats
        for i in range(600):  # overflow the 512-entry LRU bound
            cache.get_or_build(("filler", i), lambda: object())
        assert cache.stats.evictions > 0
        evictions = cache.stats.evictions
        lowers = cache.stats.lowers
        old = reset_global_plan_cache()
        assert old is cache
        # the captured stats object survives the reset untouched
        assert res.cache_stats is old.stats
        assert old.stats.evictions == evictions
        assert old.stats.lowers == lowers
        fresh = global_plan_cache()
        assert fresh is not old
        assert len(fresh) == 0 and fresh.stats.evictions == 0
    finally:
        reset_global_plan_cache()


def test_read_stage_total_over_fully_virtual_regions():
    """The read stage must materialize ANY virtual describe host-side — even
    a strip lying entirely past the image (more workers than rows): it snaps
    to the nearest edge unit and replicates outward, the same values the
    SPMD executor's edge-padded global carries over its pad rows."""
    src = SyntheticScene(3, 8, bands=2, dtype=np.float32)
    p, m = PP.p6_conversion(src)
    # 3 rows over 4 workers -> H = 1: worker 3's strip [3, 4) is fully virtual
    desc = p.describe_pull(m, ImageRegion((3, 0), (1, 8)), virtual=True)
    assert desc.pad_rows == 1
    (arr,) = desc.read_sources()
    bottom = np.asarray(src.generate(ImageRegion((2, 0), (1, 8))))
    np.testing.assert_array_equal(np.asarray(arr), bottom)
    # mixed axis: rows partially in-image, bottom spill edge-replicates
    desc2 = p.describe_pull(m, ImageRegion((1, 0), (4, 8)), virtual=True)
    (arr2,) = desc2.read_sources()
    whole = np.asarray(src.generate(ImageRegion((0, 0), (3, 8))))
    expect = np.concatenate([whole[1:], whole[2:], whole[2:]], axis=0)
    np.testing.assert_array_equal(np.asarray(arr2), expect)


def test_serial_signatures_distinct_across_pipelines():
    """Two structurally identical pipelines must not share signatures (node
    serials, not recycled ids, key the process-wide registry)."""
    def mk():
        p, m = PP.p6_conversion(SyntheticScene(24, 16, bands=1, dtype=np.float32))
        return p.describe_pull(m, p.info(m).full_region).signature

    assert mk() != mk()


# -- cross-executor sharing: streaming then pool ------------------------------
def test_pool_after_streaming_is_registry_hit():
    """Second executor on the same pipeline/geometry: hits, zero new
    compiles, zero new lowers."""
    p, m = PP.p6_conversion(SyntheticScene(64, 32, bands=3, dtype=np.float32))
    oracle = np.asarray(p.pull(m, p.info(m).full_region))
    cache = PlanCache()
    splitter = StripeSplitter(n_splits=8)
    StreamingExecutor(p, m, splitter, plan_cache=cache, prefetch=0).run()
    np.testing.assert_array_equal(m.result, oracle)
    compiles0, lowers0 = cache.stats.compiles, cache.stats.lowers
    hits0 = cache.stats.hits

    res = run_pool(p, m, splitter, n_workers=3, plan_cache=cache)
    np.testing.assert_array_equal(m.result, oracle)
    assert res.cache_stats is cache.stats
    assert cache.stats.compiles == compiles0  # zero new traces
    assert cache.stats.lowers == lowers0  # zero new closure trees
    assert cache.stats.hits == hits0 + 8  # every region a hit


def test_run_pipeline_routes_through_shared_registry():
    cache = PlanCache()
    src = SyntheticScene(48, 24, bands=2, dtype=np.float32)
    res1, m1 = PP.run_pipeline(
        "P6", src, plan_cache=cache, splitter=StripeSplitter(n_splits=6)
    )
    assert res1.cache_stats is cache.stats and cache.stats.hits == 5
    res2, m2 = PP.run_pipeline(
        "P6", src, executor="pool", n_workers=2, plan_cache=cache,
        splitter=StripeSplitter(n_splits=6),
    )
    # same source object but a fresh pipeline instance → fresh signatures;
    # within the run the uniform split still hits
    np.testing.assert_array_equal(m1.result, m2.result)
    p_or, m_or = PP.p6_conversion(src)
    np.testing.assert_array_equal(
        m1.result, np.asarray(p_or.pull(m_or, p_or.info(m_or).full_region))
    )


def test_run_pipeline_prebuilt_pair_reuses_plans_across_executors():
    """Passing the built (pipeline, mapper) pair makes cross-executor reuse
    real: the pool run after the streaming run is all registry hits."""
    cache = PlanCache()
    built = PP.p6_conversion(SyntheticScene(48, 24, bands=2, dtype=np.float32))
    PP.run_pipeline(built, plan_cache=cache, splitter=StripeSplitter(n_splits=6))
    compiles0, lowers0 = cache.stats.compiles, cache.stats.lowers
    hits0 = cache.stats.hits
    res, m = PP.run_pipeline(
        built, executor="pool", n_workers=2, plan_cache=cache,
        splitter=StripeSplitter(n_splits=6),
    )
    assert cache.stats.compiles == compiles0
    assert cache.stats.lowers == lowers0
    assert cache.stats.hits == hits0 + 6
    p_or, m_or = PP.p6_conversion(
        SyntheticScene(48, 24, bands=2, dtype=np.float32)
    )
    np.testing.assert_array_equal(
        m.result, np.asarray(p_or.pull(m_or, p_or.info(m_or).full_region))
    )


# -- registry counter consistency under concurrent races ----------------------
def _spin_barrier_run(n_threads, fn):
    barrier = threading.Barrier(n_threads)
    errors = []

    def run(w):
        try:
            barrier.wait()
            fn(w)
        except BaseException as e:  # noqa: BLE001 — surfaced below
            errors.append(e)

    threads = [threading.Thread(target=run, args=(w,)) for w in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errors, errors


def test_plan_cache_unbounded_concurrent_races_lower_once_per_signature():
    """Racing compiled_for calls may both run the lower callback, but the
    registry counts exactly one lower/miss per signature (first insert wins)
    and every other call is a hit — no signature is lowered twice in the
    stats without an eviction in between."""
    p, m = PP.p6_conversion(SyntheticScene(40, 16, bands=2, dtype=np.float32))
    info = p.info(m)
    regions = StripeSplitter(n_splits=4).split(info.full_region, info)
    descs = [p.describe_pull(m, r) for r in regions]
    # all four stripes share one signature (uniform split, no halos)
    signatures = {d.signature for d in descs}
    cache = PlanCache()
    n_threads, reps = 8, 5

    def work(w):
        for rep in range(reps):
            d = descs[(w + rep) % len(descs)]
            entry = cache.compiled_for(d, lambda d=d: p.lower_pull(d))
            assert entry is not None

    _spin_barrier_run(n_threads, work)
    total = n_threads * reps
    s = cache.stats
    assert s.hits + s.misses == total
    assert s.misses == s.lowers == len(signatures) == len(cache)
    assert s.evictions == 0


def test_plan_cache_lru_eviction_under_concurrent_get_or_build():
    """Threaded stress over more signatures than max_entries: counters stay
    consistent (hits + misses == calls, inserts == misses, evictions ==
    inserts - live entries) and re-building an evicted key is a counted miss,
    never a silent double-build of a live entry."""
    cache = PlanCache(max_entries=4)
    n_threads, n_keys, reps = 8, 12, 40
    built = []
    built_lock = threading.Lock()

    def work(w):
        rng = np.random.default_rng(w)
        for _ in range(reps):
            key = ("prog", int(rng.integers(n_keys)))

            def build(key=key):
                with built_lock:
                    built.append(key)
                return object()

            assert cache.get_or_build(key, build) is not None

    _spin_barrier_run(n_threads, work)
    s = cache.stats
    total = n_threads * reps
    assert s.hits + s.misses == total
    assert len(cache) <= 4
    assert s.evictions == s.misses - len(cache)
    # racing builds may overshoot the counted misses, but never undershoot:
    # every counted miss ran a build
    assert s.misses <= len(built)
    assert s.evictions > 0  # the stress actually exercised LRU churn


def test_plan_cache_eviction_then_rebuild_is_counted_miss():
    p, m = PP.p6_conversion(SyntheticScene(48, 16, bands=1, dtype=np.float32))
    info = p.info(m)
    # distinct stripe heights → two distinct signatures
    r0 = StripeSplitter(n_splits=2).split(info.full_region, info)[0]
    r1 = StripeSplitter(n_splits=3).split(info.full_region, info)[0]
    assert r0.size != r1.size
    cache = PlanCache(max_entries=1)
    d0, d1 = p.describe_pull(m, r0), p.describe_pull(m, r1)
    lower_calls = []

    def lower(d):
        lower_calls.append(d.signature)
        return p.lower_pull(d)

    cache.compiled_for(d0, lambda: lower(d0))
    cache.compiled_for(d1, lambda: lower(d1))  # evicts d0's entry
    assert cache.stats.evictions == 1
    cache.compiled_for(d0, lambda: lower(d0))  # rebuild after eviction
    assert lower_calls.count(d0.signature) == 2
    assert cache.stats.lowers == 3 and cache.stats.misses == 3
    assert cache.stats.hits == 0
