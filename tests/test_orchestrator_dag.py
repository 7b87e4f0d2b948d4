"""Region-granularity DAG scheduler (pipelined orchestrator) — concurrency
test harness.

Locks in the edge-queue commit protocol of :mod:`repro.core.dag`:

  * **Property** (hypothesis): random stage-DAG topologies × queue
    capacities (1–4) × worker counts × splitters produce bit-identical
    stage outputs under the pipelined scheduler and the sequential barrier
    oracle, with zero *extra* plan-cache lowers/compiles (a fresh-cache
    pipelined run records exactly the counts of a fresh-cache barrier run —
    region-granularity streaming adds no re-tracing).
  * **Deadlock/starvation regressions**: tight queue capacity + slow
    consumer + fast producer stays inside the capacity bound; halo demand
    past the frontier overdrafts instead of cycle-waiting; a producer that
    raises mid-stream cancels its consumers with the original exception;
    cancel-while-blocked unwinds promptly.  Every potentially-wedging run
    goes through an in-test watchdog (thread + join timeout + cancel) so a
    regression FAILS even without the pytest-timeout plugin, and the
    module-level ``pytest.mark.timeout`` arms the plugin's watchdog in CI.
  * **Hygiene**: ``Orchestrator.cleanup()`` / context-manager workdir
    lifecycle, :class:`~repro.core.RowCoverage` interval algebra,
    :class:`~repro.core.EdgeQueue` unit behavior.
"""
import threading
import time

import numpy as np
import pytest

from repro.core import (
    EdgeQueue,
    Orchestrator,
    Pipeline,
    PipelineCancelled,
    PlanCache,
    RowCoverage,
    Stage,
    StripeSplitter,
    TileSplitter,
    UpstreamFailed,
)
from repro.core.process_object import Filter
from repro.core.region import ImageRegion
from repro.filters import BandMath, Concat, SobelGradient, gaussian_smoothing
from repro.raster import ParallelRasterWriter, RasterReader, SyntheticScene

try:  # CI installs hypothesis via the test extras; local runs may lack it
    from hypothesis import HealthCheck, given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False

# per-test watchdog via pytest-timeout when the plugin is installed (CI);
# the in-test watchdogs below keep the suite hang-free without it
pytestmark = pytest.mark.timeout(120)

ROWS, COLS = 24, 16


# -- helpers ------------------------------------------------------------------
def run_watchdogged(orch: Orchestrator, timeout: float = 60.0, **kw):
    """Run the orchestrator on a helper thread; a wedge FAILS the test
    (after a best-effort cancel) instead of hanging the suite."""
    box: dict = {}

    def target():
        try:
            box["result"] = orch.run(**kw)
        except BaseException as exc:  # noqa: BLE001 — re-raised on the test thread
            box["error"] = exc

    t = threading.Thread(target=target, daemon=True)
    t.start()
    t.join(timeout)
    if t.is_alive():
        orch.cancel()
        t.join(10)
        pytest.fail(f"orchestrator run wedged (>{timeout}s)")
    if "error" in box:
        raise box["error"]
    return box["result"]


class _SleepFilter(Filter):
    """Identity with a fixed host-side per-region cost (``use_jit=False``
    stages only — under jit the sleep would run once, at trace time)."""

    def __init__(self, seconds: float, name=None):
        super().__init__(name)
        self.seconds = seconds

    def output_info(self, info):
        return info

    def generate(self, out_region, x):
        time.sleep(self.seconds)
        return x


class _FailAtRow(Filter):
    """Identity that raises once the region origin reaches ``fail_row``."""

    def __init__(self, fail_row: int, message: str, name=None):
        super().__init__(name)
        self.fail_row = fail_row
        self.message = message

    def output_info(self, info):
        return info

    def generate(self, out_region, x):
        if out_region.row0 >= self.fail_row:
            raise RuntimeError(self.message)
        return x


def _stage(name, inputs, mid_filters, *, n_workers=1, n_splits=4,
           use_jit=True, seed=7, rows=ROWS, cols=COLS):
    """A pool Stage: readers (Concat on fan-in) → mid filters → 2-band
    projection → commit-capable writer.  The projection keeps every stage on
    one band count so any stage can feed any other."""

    def build(input_paths, out_path):
        p = Pipeline()
        if inputs:
            ins = [p.add(RasterReader(input_paths[i])) for i in inputs]
            x = ins[0] if len(ins) == 1 else p.add(Concat(len(ins)), ins)
        else:
            x = p.add(SyntheticScene(rows, cols, bands=2, dtype=np.float32,
                                     seed=seed))
        for f in mid_filters():
            x = p.add(f, [x])
        x = p.add(BandMath(_two_bands, out_bands=2), [x])
        m = p.add(ParallelRasterWriter(out_path), [x])
        return p, m

    return Stage(name, build, inputs=tuple(inputs), n_workers=n_workers,
                 splitter=StripeSplitter(n_splits=n_splits), use_jit=use_jit)


def _two_bands(a):
    import jax.numpy as jnp

    return jnp.concatenate([a, a], axis=-1)[..., :2]


_KINDS = {
    "smooth": lambda: [gaussian_smoothing(1.0)],   # halo reads
    "sobel": lambda: [SobelGradient()],            # halo reads, 1-band mid
    "scale": lambda: [],                           # pointwise only
}


def _run_both(stages_fn, queue_capacity=2, max_workers=None, timeout=120.0):
    """Barrier oracle and pipelined run on fresh caches; returns
    (outputs_barrier, outputs_pipelined, cache_barrier, cache_pipelined,
    edge_stats)."""
    cache_b, cache_p = PlanCache(), PlanCache()
    with Orchestrator(stages_fn(), plan_cache=cache_b) as orch:
        res = run_watchdogged(orch, timeout)
        barrier = {k: RasterReader(v.path).read_region() for k, v in res.items()}
    with Orchestrator(stages_fn(), plan_cache=cache_p, pipelined=True,
                      queue_capacity=queue_capacity,
                      max_workers=max_workers) as orch:
        res = run_watchdogged(orch, timeout)
        pipelined = {k: RasterReader(v.path).read_region() for k, v in res.items()}
        stats = dict(orch.edge_stats)
    return barrier, pipelined, cache_b, cache_p, stats


# -- property: random DAGs are bit-identical to the barrier oracle ------------
def _check_dag_case(spec, capacity):
    """Any topology × capacity × workers × splits: pipelined output is
    bit-identical to the barrier oracle and adds zero extra plan-cache
    lowers/compiles (fresh-cache counts match exactly)."""

    def stages():
        return [
            _stage(f"s{i}", [f"s{j}" for j in inputs], _KINDS[kind],
                   n_workers=n_workers, n_splits=n_splits)
            for i, (inputs, kind, n_workers, n_splits) in enumerate(spec)
        ]

    barrier, pipelined, cache_b, cache_p, stats = _run_both(
        stages, queue_capacity=capacity)
    assert set(barrier) == set(pipelined)
    for name in barrier:
        np.testing.assert_array_equal(
            pipelined[name], barrier[name],
            err_msg=f"stage {name} diverged from the barrier oracle "
                    f"(spec={spec}, capacity={capacity})")
    assert cache_p.stats.lowers == cache_b.stats.lowers, (
        spec, cache_b.stats, cache_p.stats)
    assert cache_p.stats.compiles == cache_b.stats.compiles, (
        spec, cache_b.stats, cache_p.stats)
    assert all(s.offers > 0 for s in stats.values())


if HAVE_HYPOTHESIS:

    @st.composite
    def dag_specs(draw):
        n_stages = draw(st.integers(2, 4))
        spec = []
        for i in range(n_stages):
            if i == 0:
                inputs = ()
            else:
                k = draw(st.integers(1, min(2, i)))
                inputs = tuple(
                    draw(st.lists(st.sampled_from(range(i)), min_size=k,
                                  max_size=k, unique=True).map(sorted))
                )
            kind = draw(st.sampled_from(sorted(_KINDS)))
            n_workers = draw(st.integers(1, 3))
            n_splits = draw(st.integers(2, 6))
            spec.append((inputs, kind, n_workers, n_splits))
        capacity = draw(st.integers(1, 4))
        return spec, capacity

    @settings(max_examples=8, deadline=None,
              suppress_health_check=[HealthCheck.too_slow])
    @given(dag_specs())
    def test_random_dag_pipelined_equals_barrier(case):
        _check_dag_case(*case)

else:

    @pytest.mark.skip(reason="hypothesis not installed")
    def test_random_dag_pipelined_equals_barrier():
        pass


def test_diamond_dag_pipelined_equals_barrier():
    """Deterministic fan-out/fan-in cover (runs even without hypothesis):
    source → {smooth, sobel} → concat sink, mixed worker counts and ragged
    splits, capacity 1."""
    _check_dag_case(
        [((), "scale", 2, 5),
         ((0,), "smooth", 1, 3),
         ((0,), "sobel", 2, 4),
         ((1, 2), "scale", 3, 6)],
        capacity=1,
    )


# -- deadlock/starvation regressions ------------------------------------------
def _chain(consumer_sleep=0.0, producer_sleep=0.0, n_splits=8, use_jit=False,
           consumer_filters=()):
    def stages():
        return [
            _stage("produce", [],
                   (lambda: [_SleepFilter(producer_sleep)])
                   if producer_sleep else (lambda: []),
                   n_splits=n_splits, use_jit=use_jit),
            _stage("consume", ["produce"],
                   lambda: list(consumer_filters)
                   + ([_SleepFilter(consumer_sleep)] if consumer_sleep else []),
                   n_splits=n_splits, use_jit=use_jit),
        ]

    return stages


def test_tight_capacity_slow_consumer_fast_producer():
    """capacity=1 + fast producer + slow consumer: the producer is paced to
    the commit frontier — at most one zero-halo strip in flight, no
    overdrafts, outputs bit-identical to the barrier oracle."""
    barrier, pipelined, _, _, stats = _run_both(
        _chain(consumer_sleep=0.02), queue_capacity=1, timeout=60.0)
    for name in barrier:
        np.testing.assert_array_equal(pipelined[name], barrier[name])
    (edge,) = stats.values()
    assert edge.max_in_flight <= 1, edge
    assert edge.overdrafts == 0, edge
    assert edge.commits > 0 and edge.releases > 0, edge


def test_halo_demand_overdrafts_instead_of_deadlocking():
    """capacity=1 + a halo consumer: region 0 needs rows past the only
    in-flight strip, which must overdraft (bounded, demand-driven) rather
    than cycle-wait — and outputs still match the oracle exactly."""
    barrier, pipelined, _, _, stats = _run_both(
        _chain(consumer_filters=(gaussian_smoothing(1.0),)),
        queue_capacity=1, timeout=60.0)
    for name in barrier:
        np.testing.assert_array_equal(pipelined[name], barrier[name])
    (edge,) = stats.values()
    assert edge.overdrafts >= 1, edge  # the halo demand forced the overdraft
    assert edge.max_in_flight <= 3, edge  # ...but stayed demand-bounded


def test_producer_failure_cancels_consumers_with_original_exception():
    """A producer that raises mid-stream must fail the whole run with ITS
    exception — consumers unwind via UpstreamFailed instead of hanging on
    rows that will never commit."""

    def stages():
        return [
            _stage("produce", [], lambda: [_FailAtRow(ROWS // 2, "boom-mid")],
                   n_splits=8, use_jit=False),
            _stage("consume", ["produce"], lambda: [_SleepFilter(0.01)],
                   n_splits=8, use_jit=False),
        ]

    with Orchestrator(stages(), pipelined=True, queue_capacity=1) as orch:
        with pytest.raises(RuntimeError, match="boom-mid"):
            run_watchdogged(orch, timeout=60.0)


def test_consumer_failure_unblocks_backpressured_producer():
    """The inverse direction: a consumer that raises must wake a producer
    blocked on backpressure (PipelineCancelled), and the run surfaces the
    consumer's original exception as the root cause."""

    def stages():
        return [
            _stage("produce", [], lambda: [_SleepFilter(0.005)],
                   n_splits=8, use_jit=False),
            _stage("consume", ["produce"],
                   lambda: [_FailAtRow(ROWS // 2, "consumer-boom")],
                   n_splits=8, use_jit=False),
        ]

    with Orchestrator(stages(), pipelined=True, queue_capacity=1) as orch:
        with pytest.raises(RuntimeError, match="consumer-boom"):
            run_watchdogged(orch, timeout=60.0)


def test_cancel_while_blocked_unwinds_promptly():
    """Orchestrator.cancel() during a pipelined run: blocked producers and
    consumers unwind with PipelineCancelled well before the run would have
    finished on its own."""
    per_region, n_splits = 0.25, 12

    def stages():
        return [
            _stage("produce", [], lambda: [_SleepFilter(per_region)],
                   n_splits=n_splits, use_jit=False, rows=48),
            _stage("consume", ["produce"], lambda: [],
                   n_splits=n_splits, use_jit=False, rows=48),
        ]

    orch = Orchestrator(stages(), pipelined=True, queue_capacity=1)
    try:
        box: dict = {}

        def target():
            try:
                orch.run()
            except BaseException as exc:  # noqa: BLE001
                box["error"] = exc

        t0 = time.perf_counter()
        t = threading.Thread(target=target, daemon=True)
        t.start()
        time.sleep(0.4)
        orch.cancel()
        t.join(20)
        elapsed = time.perf_counter() - t0
        assert not t.is_alive(), "cancelled run did not unwind"
        assert isinstance(box.get("error"), PipelineCancelled), box.get("error")
        # full run = 12 regions x 0.25s producer alone; cancel cut it short
        assert elapsed < per_region * n_splits * 0.8, elapsed
    finally:
        orch.cleanup()


def test_pipelined_rejects_tile_split_producers():
    """Row-granularity commits need full-width strips: a tiled producer is a
    loud ValueError, not silent corruption."""

    def stages():
        s = _stage("produce", [], lambda: [], use_jit=False)
        s = Stage(s.name, s.build, splitter=TileSplitter(2, 2), use_jit=False)
        return [
            s,
            _stage("consume", ["produce"], lambda: [], use_jit=False),
        ]

    with Orchestrator(stages(), pipelined=True) as orch:
        with pytest.raises(ValueError, match="full-width"):
            run_watchdogged(orch, timeout=60.0)


def test_worker_budget_shared_across_stages():
    """max_workers caps concurrently-running stage workers; the run still
    completes bit-identically (budget waits point up the DAG, no cycle)."""
    barrier, pipelined, _, _, _ = _run_both(
        _chain(consumer_sleep=0.005), queue_capacity=2, max_workers=2,
        timeout=60.0)
    for name in barrier:
        np.testing.assert_array_equal(pipelined[name], barrier[name])


# -- workdir lifecycle --------------------------------------------------------
def _single_stage():
    return [_stage("only", [], lambda: [], use_jit=False)]


def test_cleanup_removes_owned_workdir():
    orch = Orchestrator(_single_stage())
    assert orch.workdir.exists()
    orch.run()
    orch.cleanup()
    assert not orch.workdir.exists()
    orch.cleanup()  # idempotent


def test_context_manager_removes_owned_workdir():
    with Orchestrator(_single_stage()) as orch:
        wd = orch.workdir
        orch.run()
        assert wd.exists()
    assert not wd.exists()


def test_cleanup_keeps_caller_supplied_workdir(tmp_path):
    with Orchestrator(_single_stage(), workdir=str(tmp_path)) as orch:
        orch.run()
    assert tmp_path.exists()  # caller-owned: left alone


# -- validation ---------------------------------------------------------------
def test_orchestrator_validates_pipelining_args():
    with pytest.raises(ValueError, match="queue_capacity"):
        Orchestrator(_single_stage(), queue_capacity=0)
    with pytest.raises(ValueError, match="max_workers"):
        Orchestrator(_single_stage(), max_workers=0)


def test_upstream_failed_unwraps_to_root_cause():
    root = ValueError("root")
    nested = UpstreamFailed("b", UpstreamFailed("a", root))
    assert nested.stage == "a"
    assert nested.cause is root


# -- EdgeQueue units ----------------------------------------------------------
def test_edge_queue_rejects_tile_offers_and_bad_capacity():
    with pytest.raises(ValueError, match="capacity"):
        EdgeQueue("p", "c", capacity=0)
    q = EdgeQueue("p", "c", capacity=1)
    with pytest.raises(ValueError, match="full-width"):
        q.offer(ImageRegion((0, 4), (4, 4)))


def test_fanout_admits_a_strip_a_consumer_of_another_edge_waits_on():
    """A producer with two outgoing edges, one at capacity: while the other
    edge's consumer waits on the next strip's rows, the strip is admitted to
    both edges (an overdraft on the full one) and the wait ends once it is
    committed; admitted edge by edge, the offer blocked for ever."""
    from repro.core.dag import EdgeFanout

    a, b = EdgeQueue("p", "a", capacity=1), EdgeQueue("p", "b", capacity=1)
    fan = EdgeFanout([a, b])
    fan.opened(type("Info", (), {"rows": 8})())
    for e in (a, b):
        e.consumer_started()
    b.offer(ImageRegion((0, 0), (4, 4)))  # b at capacity, its consumer busy
    waiter = threading.Thread(target=a.wait_rows, args=(4, 8), daemon=True)
    waiter.start()
    offer = threading.Thread(
        target=fan.offer, args=(ImageRegion((4, 0), (4, 4)),), daemon=True
    )
    offer.start()
    offer.join(5)
    assert not offer.is_alive(), "the fanout's offer wedged"
    assert (a.stats.offers, b.stats.offers, b.stats.overdrafts) == (1, 2, 1)
    fan.commit(4, 8)
    waiter.join(5)
    assert not waiter.is_alive()


def test_edge_queue_wait_rows_detects_missing_commit_hook():
    q = EdgeQueue("p", "c", capacity=1)
    q.open(8)
    q.close_producer()  # producer "done" without ever committing rows
    # close_producer marks all rows committed (normal completion)...
    q.wait_rows(0, 8)
    # ...but a producer that dies before open+close leaves waiters failing
    q2 = EdgeQueue("p", "c", capacity=1)
    q2.open(8)
    q2.fail("p", RuntimeError("dead"))
    with pytest.raises(UpstreamFailed) as ei:
        q2.wait_rows(0, 4)
    assert ei.value.stage == "p"
    assert "dead" in repr(ei.value.cause)


def test_edge_queue_cancel_wakes_blocked_consumer():
    q = EdgeQueue("p", "c", capacity=1)
    q.open(8)
    box: dict = {}

    def waiter():
        try:
            q.wait_rows(0, 8)
        except BaseException as exc:  # noqa: BLE001
            box["error"] = exc

    t = threading.Thread(target=waiter, daemon=True)
    t.start()
    time.sleep(0.05)
    q.cancel(PipelineCancelled("stop"))
    t.join(10)
    assert not t.is_alive()
    assert isinstance(box.get("error"), PipelineCancelled)


def test_edge_queue_commit_coverage_gates_waits():
    q = EdgeQueue("p", "c", capacity=4)
    q.open(16)
    q.consumer_started()
    q.commit(0, 8)
    q.wait_rows(0, 8)  # returns immediately: covered
    q.commit(8, 16)
    q.wait_rows(4, 12)  # spans both committed runs
    assert q.stats.commits == 2


# -- RowCoverage algebra ------------------------------------------------------
def test_row_coverage_matches_set_oracle():
    """Randomized out-of-order interval commits (seeded, no hypothesis
    needed) match a set-of-rows oracle, and the interval list stays sorted,
    disjoint and non-adjacent."""
    rng = np.random.default_rng(0)
    for _ in range(200):
        cov = RowCoverage()
        model: set = set()
        for _ in range(rng.integers(0, 20)):
            lo, hi = int(rng.integers(0, 40)), int(rng.integers(0, 40))
            cov.add(lo, hi)
            model.update(range(lo, hi))
        assert cov.covered_rows() == len(model)
        for _ in range(10):
            lo, hi = int(rng.integers(0, 40)), int(rng.integers(0, 40))
            expected = hi <= lo or all(r in model for r in range(lo, hi))
            assert cov.covers(lo, hi) == expected, (cov.intervals(), lo, hi)
        ivals = cov.intervals()
        assert all(a < b for a, b in ivals)
        assert all(
            ivals[i][1] < ivals[i + 1][0] for i in range(len(ivals) - 1)
        )
