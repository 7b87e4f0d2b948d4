"""Streaming engine (paper §II.B): pull the pipeline region by region.

The mapper picks a splitting strategy, then the engine processes regions on a
bounded memory footprint.  ``worker`` / ``n_workers`` select this worker's
slice of the schedule, so the same driver runs standalone or as one rank of a
host-level parallel run (e.g. one process per pod host feeding its devices).

Three layers make the hot loop run at hardware speed:

  1. **Canonical plans** — the describe pass (``Pipeline.describe_pull``)
     folds every shape/boundary-static quantity into a plan signature; the
     lower pass builds the closure threading absolute coordinates
     (``needs_origin``) and persistent-filter state through the pure function
     as traced arguments.  Drifting warp requests are classified as
     *windowed reads* (static-shape bounding windows, traced origins — see
     ``ProcessObject.window_bound``), so a striped warp run shares ONE
     signature across every stripe, borders included.
  2. **PlanCache** — the shared compiled-plan registry of the ExecutionPlan
     layer (:mod:`repro.core.execplan`), keyed by plan signature.  A uniform
     stripe split compiles exactly ONCE: border stripes describe against the
     virtual padded geometry (no row clamping — the halo spill is
     materialized by edge replication at the read stage, exactly like
     windowed reads and the SPMD prober), so top/interior/bottom all share
     the interior entry.  Pipelines whose persistent filters are not
     mask-aware, or whose halo requests land on intermediate filters
     (stacked neighborhood filters — see ``Pipeline.virtual_rows_safe``),
     keep exact clamped describes (one entry per border
     geometry).  Registry *hits* run the cheap describe pass only — the
     lower pass (closure construction) happens on misses.
     Hit/miss/compile/lower/eviction counts are surfaced in
     ``StreamResult.cache_stats``; the same registry serves the SPMD
     :class:`~repro.core.parallel.ParallelExecutor`, whose virtual padded
     strips land on the very same interior entries (the shared read stage,
     :func:`~repro.core.execplan.read_plan_sources`, clamps + edge-pads any
     virtual row spill host-side, mirroring the SPMD halo replication), so
     streaming→SPMD stays a registry hit on ragged and n=2 splits too.
  3. **Async double buffering** — with ``prefetch=k``, source reads for the
     next ``k`` regions run on a thread pool while the device computes the
     current one, and ``mapper.consume`` is handed to a background writer
     behind a bounded queue.  Windowed reads prefetch the full static-shape
     window (edge-replicating any border spill host-side), so the hot loop
     feeds fixed-shape buffers to one compiled function.  In-flight memory
     stays bounded at roughly ``2·prefetch + 2`` region buffers (k
     read-ahead + one computing + k + 1 queued writes), preserving the
     paper's memory-budget guarantee with a constant factor.

Pipelines containing :class:`PersistentFilter` nodes run through the compiled
path too: state is carried across regions as
``fn(arrays, pstates, origins) -> (pixels, new_pstates)``.

The seed semantics stay reachable for A/B: ``use_jit=False`` is the eager
pull, and ``cache=False`` restores the per-region re-jit behavior.

``run_pool`` is the single-host concurrent driver: ``n_workers`` threads
drain one shared :class:`~repro.core.scheduling.WorkStealingQueue` (or their
static/LPT slices) against a shared :class:`PlanCache` — the dynamic load
balancing the paper names as future work (§IV.C).
"""
from __future__ import annotations

import collections
import dataclasses
import queue
import threading
from concurrent.futures import ThreadPoolExecutor
from typing import Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.execplan import (  # noqa: F401 — re-exported for back-compat
    CacheStats,
    PlanCache,
    _CompiledEntry,
)
from repro.core.pipeline import Pipeline
from repro.core.process_object import Mapper, PersistentFilter
from repro.core.region import ImageRegion
from repro.core.scheduling import (
    FifoQueue,
    WorkStealingQueue,
    lpt_schedule,
    static_schedule,
    work_stealing_schedule,
)
from repro.core.splitting import Splitter, StripeSplitter
from repro.core.tracing import span

_SCHEDULERS = ("static", "lpt", "work_stealing")


def _virtual_describe_mode(pipeline: Pipeline) -> "bool | str":
    """The virtual describe mode the streaming drivers use for every strip
    or tile: ``"grid"`` (no clamping in either axis), ``"rows"`` (rows only)
    or ``False`` (exact clamped describes).  Structural conditions, decided
    by :meth:`Pipeline.virtual_describe_mode`:

      * any persistent filter must be mask-aware — under virtual geometry a
        border region's accumulation can include edge-replicated pad pixels
        that only a validity mask (``supports_mask``) keeps out of the
        reduction;
      * every spilling halo request on the virtualized axis must land
        directly on a source (:meth:`Pipeline.virtual_rows_safe` /
        :meth:`Pipeline.virtual_cols_safe`) — a halo landing on an
        intermediate filter (stacked neighborhood filters) is clamped and
        output-replicated by the exact walk but *computed* from replicated
        source rows by the virtual walk, so those pipelines keep the exact
        per-border describes to preserve the eager oracle's border pixels.

    The SPMD tile prober (:func:`repro.core.parallel.build_tile_plan`) takes
    its mode from the same method, so a streaming warm-up and a subsequent
    grid run land on one registry entry."""
    return pipeline.virtual_describe_mode()


class _WriteBehind:
    """Hands ``consume`` to a background thread through a bounded queue (the
    write-behind half of the double buffer).  On a consume error the thread
    keeps draining so producers never deadlock; the error re-raises on the
    producer side at the next ``put`` or at ``close``."""

    _STOP = object()

    def __init__(self, consume: Callable[[ImageRegion, np.ndarray], None], depth: int):
        self._consume = consume
        self._q: "queue.Queue" = queue.Queue(maxsize=max(1, depth))
        self._error: Optional[BaseException] = None
        self._thread = threading.Thread(
            target=self._loop, name="write-behind", daemon=True
        )
        self._thread.start()

    def _loop(self):
        while True:
            item = self._q.get()
            if item is self._STOP:
                return
            if self._error is not None:
                continue  # drain without consuming
            try:
                self._consume(*item)
            except BaseException as e:  # noqa: BLE001 — must cross threads
                self._error = e

    def put(self, region: ImageRegion, data: np.ndarray) -> None:
        if self._error is not None:
            raise self._error
        self._q.put((region, data))

    def close(self) -> None:
        self._q.put(self._STOP)
        self._thread.join()
        if self._error is not None:
            raise self._error


@dataclasses.dataclass
class StreamResult:
    regions_processed: int
    pixels_processed: int
    persistent_results: Dict[str, Dict[str, jnp.ndarray]]
    #: per-region pixel outputs, only kept when ``keep_outputs=True``
    outputs: Optional[List[np.ndarray]] = None
    #: plan-cache counters for this run (None on the eager / re-jit paths).
    #: This is the LIVE CacheStats object — it keeps counting after the run
    #: (documented behavior, see ``reset_global_plan_cache``).
    cache_stats: Optional[CacheStats] = None


class StreamingExecutor:
    def __init__(
        self,
        pipeline: Pipeline,
        mapper: Mapper,
        splitter: Optional[Splitter] = None,
        worker: int = 0,
        n_workers: int = 1,
        scheduler: str = "static",
        cost_fn: Optional[Callable[[ImageRegion], float]] = None,
        use_jit: bool = True,
        cache: bool = True,
        plan_cache: Optional[PlanCache] = None,
        prefetch: int = 2,
        max_cached_plans: Optional[int] = None,
        region_gate=None,
    ):
        if scheduler not in _SCHEDULERS:
            raise ValueError(scheduler)
        self.pipeline = pipeline
        self.mapper = mapper
        self.splitter = splitter or StripeSplitter(n_splits=max(1, n_workers) * 4)
        self.worker = worker
        self.n_workers = n_workers
        self.scheduler = scheduler
        self.cost_fn = cost_fn or (lambda r: float(r.num_pixels))
        self.use_jit = use_jit
        self.cache = cache
        # explicit None check: an empty PlanCache is falsy (it has __len__)
        self.plan_cache = (
            plan_cache if plan_cache is not None else PlanCache(max_cached_plans)
        )
        self.prefetch = max(0, int(prefetch))
        # region-availability gate (pipelined stage DAGs): wait(desc) blocks
        # until the rows the region reads are committed upstream; done(desc)
        # releases them once the region's output has been handed off
        self.region_gate = region_gate
        # Border strips describe against the virtual padded geometry (like the
        # SPMD prober), so a striped halo run shares ONE interior signature:
        # the row spill of border halos is materialized at the read stage
        # instead of being clamped into a per-border plan.  Persistent filters
        # that are not mask-aware would accumulate the replicated pad rows, so
        # those pipelines keep the exact clamped describes.
        self.describe_virtual = _virtual_describe_mode(pipeline)

    def my_regions(self) -> List[ImageRegion]:
        info = self.pipeline.info(self.mapper)
        regions = self.splitter.split(info.full_region, info)
        if self.scheduler == "static":
            sched = static_schedule(regions, self.n_workers)
        elif self.scheduler == "lpt":
            sched = lpt_schedule(regions, self.n_workers, self.cost_fn)
        else:
            sched = work_stealing_schedule(regions, self.n_workers, self.cost_fn)
        return [regions[i] for i in sched[self.worker]]

    # -- the prefetch stage: host-side planning + source reads ----------------
    def _prepare(self, region: ImageRegion):
        # describe pass only; the O(graph) closure tree is lowered by the
        # registry on misses — cache hits never rebuild it.  Virtual geometry
        # (when safe) folds border strips onto the interior signature.
        with span("describe", row0=region.row0, col0=region.col0):
            desc = self.pipeline.describe_pull(
                self.mapper, region, virtual=self.describe_virtual
            )
        if self.region_gate is not None:
            # block (on the prefetch thread) until the input rows this region
            # actually reads are committed by the upstream stage
            self.region_gate.wait(desc)
        fn = self.plan_cache.compiled_for(
            desc, lambda: self.pipeline.lower_pull(desc)
        )
        with span("read", row0=region.row0, col0=region.col0) as sp:
            arrays = desc.read_sources()
            sp.set_metadata(bytes=sum(a.nbytes for a in arrays),
                            inputs=len(arrays))
        return desc, fn, arrays

    def run(self, keep_outputs: bool = False) -> StreamResult:
        pipeline, mapper = self.pipeline, self.mapper
        info = pipeline.info(mapper)
        with span("begin"):
            mapper.begin(info)

        # persistent-filter state lives across regions (paper's Reset)
        pstates = {p.name: p.reset() for p in pipeline.persistent_nodes()}

        def hook(node: PersistentFilter, region: ImageRegion, inputs):
            pstates[node.name] = node.accumulate(pstates[node.name], region, *inputs)

        outputs: List[np.ndarray] = []
        pixels = 0
        regions = self.my_regions()
        compiled_path = self.use_jit and self.cache

        # hand the region schedule to range-readable sources before the
        # region loop: tiled/remote sources (RasterSource.read_ahead)
        # prefetch the covering tiles on their own thread, overlapping range
        # fetches with plan execution.  A best-effort hint — sources clamp
        # the schedule to their own geometry and plain sources ignore it.
        for src in pipeline.sources():
            ra = getattr(src, "read_ahead", None)
            if callable(ra):
                ra(regions)

        def compute(prep) -> np.ndarray:
            nonlocal pstates
            plan, fn, arrays = prep
            r = plan.out_region
            # the device's own time is busy, not idle: inside ``dispatch``
            # only the program's launch and completion latency read as idle
            with span("dispatch", row0=r.row0, col0=r.col0):
                out, pstates = fn.launch(arrays, pstates, plan.origins())
                if self.region_gate is not None:
                    # pacing-only release (the data lives on disk): fire once
                    # the region's pixels are produced and handed to the
                    # write stage
                    self.region_gate.done(plan)
                jax.block_until_ready(out)
            with span("d2h", row0=r.row0, col0=r.col0) as sp:
                data = fn.to_host(out)
                sp.set_metadata(bytes=data.nbytes)
                return data

        def produce_sync(region: ImageRegion) -> np.ndarray:
            if compiled_path:
                with span("wait_inputs", row0=region.row0, col0=region.col0):
                    prep = self._prepare(region)
                return compute(prep)
            if self.region_gate is not None:
                # non-compiled paths still gate on the described reads (the
                # gate clamps virtual row spill to the committed extent)
                desc = pipeline.describe_pull(
                    mapper, region, virtual=self.describe_virtual
                )
                self.region_gate.wait(desc)
                self.region_gate.done(desc)
            if self.use_jit and not pipeline.persistent_nodes():
                # cache=False A/B baseline: the seed's per-region re-jit
                plan = pipeline.compile_pull(mapper, region)
                return np.asarray(jax.jit(plan.fn)(plan.read_sources()))
            # eager pull; the hook observes every region exactly once
            return np.asarray(pipeline.pull(mapper, region, persistent_hook=hook))

        try:
            if compiled_path and self.prefetch > 0 and len(regions) > 1:
                pixels = self._run_async(regions, compute, outputs, keep_outputs)
            else:
                for region in regions:
                    data = produce_sync(region)
                    with span("wait_write", row0=region.row0, col0=region.col0):
                        mapper.consume(region, data)
                    pixels += region.num_pixels
                    if keep_outputs:
                        outputs.append(data)
        except BaseException:
            try:
                mapper.end()  # release writer descriptors on the error path
            except Exception:
                pass
            raise

        # paper's Synthesis: finalize persistent state after the region loop
        presults = {
            p.name: p.synthesize(pstates[p.name]) for p in pipeline.persistent_nodes()
        }
        with span("end"):
            mapper.end()
        return StreamResult(
            regions_processed=len(regions),
            pixels_processed=pixels,
            persistent_results=presults,
            outputs=outputs if keep_outputs else None,
            cache_stats=self.plan_cache.stats if compiled_path else None,
        )

    def _run_async(self, regions, compute, outputs, keep_outputs) -> int:
        """Double-buffered loop: reads for region i+1..i+prefetch overlap the
        device computing region i; writes trail behind on their own thread."""
        depth = self.prefetch
        pixels = 0
        writer = _WriteBehind(self.mapper.consume, depth + 1)
        pending: "collections.deque" = collections.deque()
        nxt = 0
        error: Optional[BaseException] = None
        with ThreadPoolExecutor(
            max_workers=depth, thread_name_prefix="prefetch"
        ) as pool:

            def fill():
                nonlocal nxt
                while nxt < len(regions) and len(pending) < depth:
                    pending.append(
                        (regions[nxt], pool.submit(self._prepare, regions[nxt]))
                    )
                    nxt += 1

            try:
                fill()
                while pending:
                    region, fut = pending.popleft()
                    with span("wait_inputs", row0=region.row0, col0=region.col0):
                        prep = fut.result()
                    fill()  # keep the read window full while we compute
                    data = compute(prep)
                    pixels += region.num_pixels
                    if keep_outputs:
                        outputs.append(data)
                    with span("wait_write", row0=region.row0, col0=region.col0):
                        writer.put(region, data)
            except BaseException as e:  # noqa: BLE001 — re-raised below
                error = e
            finally:
                for _, fut in pending:
                    fut.cancel()
                try:
                    with span("wait_write"):
                        writer.close()
                except BaseException as e:  # noqa: BLE001
                    if error is None:
                        error = e
        if error is not None:
            raise error
        return pixels


def run_pool(
    pipeline: Pipeline,
    mapper: Mapper,
    splitter: Optional[Splitter] = None,
    *,
    n_workers: int = 1,
    scheduler: str = "work_stealing",
    cost_fn: Optional[Callable[[ImageRegion], float]] = None,
    use_jit: bool = True,
    plan_cache: Optional[PlanCache] = None,
    keep_outputs: bool = False,
    region_gate=None,
    in_order: bool = False,
) -> StreamResult:
    """Run one pipeline with ``n_workers`` concurrent threads on this host.

    With ``scheduler="work_stealing"`` the workers drain one shared
    :class:`WorkStealingQueue` (idle workers steal from the most-loaded
    victim's tail); ``"static"`` / ``"lpt"`` give each worker its precomputed
    slice but still run the slices concurrently.  All workers share one
    :class:`PlanCache`, so a uniform split still compiles once.  Per-worker
    persistent states are combined with the filters' reductions, then
    synthesized once — the thread-level analogue of the paper's MPI
    many-to-one Synthesis.

    ``region_gate`` (pipelined stage DAGs, :mod:`repro.core.dag`) makes the
    workers block *per region*: each region's describe pass runs first, the
    gate waits until the input rows it reads are committed upstream, and the
    gate releases them after the region's output is consumed.  Gated runs
    hand regions out in strict region order (:class:`FifoQueue`) regardless
    of ``scheduler`` — readiness follows the producer's commit frontier, so
    in-order hand-out keeps every worker on ready (or soonest-ready) regions
    and the per-edge in-flight window bounded.  ``in_order=True`` forces the
    same FIFO hand-out on an *ungated* run: the pipelined orchestrator sets
    it on producer stages so strips are offered downstream in the consumers'
    row order and backpressure tracks the real commit frontier instead of a
    work-stealing shuffle."""
    if scheduler not in _SCHEDULERS:
        raise ValueError(scheduler)
    n_workers = max(1, int(n_workers))
    info = pipeline.info(mapper)  # also primes the metadata cache (thread-shared)
    splitter = splitter or StripeSplitter(n_splits=n_workers * 4)
    regions = splitter.split(info.full_region, info)
    cost = cost_fn or (lambda r: float(r.num_pixels))
    cache = plan_cache if plan_cache is not None else PlanCache()

    mapper.begin(info)
    consume_lock = (
        None if getattr(mapper, "thread_safe", False) else threading.Lock()
    )

    def consume(region, data):
        if consume_lock is None:
            mapper.consume(region, data)
        else:
            with consume_lock:
                mapper.consume(region, data)

    persistent = pipeline.persistent_nodes()
    # same border-strip virtualization as StreamingExecutor._prepare: all
    # workers then land on the one interior signature (single lower+compile)
    describe_virtual = _virtual_describe_mode(pipeline)
    worker_states = [{p.name: p.reset() for p in persistent} for _ in range(n_workers)]
    counts = [0] * n_workers
    pixel_counts = [0] * n_workers
    outputs_by_index: Optional[Dict[int, np.ndarray]] = {} if keep_outputs else None

    if region_gate is not None or in_order:
        fifo = FifoQueue(len(regions))

        def indices(w):
            while True:
                i = fifo.take(w)
                if i is None:
                    return
                yield i

    elif scheduler == "work_stealing":
        wsq = WorkStealingQueue(
            len(regions), n_workers, costs=[cost(r) for r in regions]
        )

        def indices(w):
            while True:
                i = wsq.take(w)
                if i is None:
                    return
                yield i

    else:
        sched = (
            static_schedule(regions, n_workers)
            if scheduler == "static"
            else lpt_schedule(regions, n_workers, cost)
        )

        def indices(w):
            return iter(sched[w])

    def work(w: int) -> None:
        pstates = worker_states[w]

        def hook(node, reg, inputs):
            pstates[node.name] = node.accumulate(pstates[node.name], reg, *inputs)

        for i in indices(w):
            region = regions[i]
            desc = None
            if use_jit or region_gate is not None:
                desc = pipeline.describe_pull(
                    mapper, region, virtual=describe_virtual
                )
                if region_gate is not None:
                    region_gate.wait(desc)  # block until input rows commit
            if use_jit:
                fn = cache.compiled_for(desc, lambda: pipeline.lower_pull(desc))
                data, pstates = fn.host(
                    desc.read_sources(), pstates, desc.origins()
                )
            else:
                data = np.asarray(
                    pipeline.pull(mapper, region, persistent_hook=hook)
                )
            consume(region, data)
            if region_gate is not None:
                region_gate.done(desc)  # region consumed: release input rows
            counts[w] += 1
            pixel_counts[w] += region.num_pixels
            if outputs_by_index is not None:
                outputs_by_index[i] = data
        worker_states[w] = pstates

    try:
        if n_workers == 1:
            work(0)
        else:
            with ThreadPoolExecutor(
                max_workers=n_workers, thread_name_prefix="pool"
            ) as pool:
                futs = [pool.submit(work, w) for w in range(n_workers)]
                for f in futs:
                    f.result()
    except BaseException:
        try:
            mapper.end()  # release writer descriptors on the error path
        except Exception:
            pass
        raise

    combined = {p.name: worker_states[0][p.name] for p in persistent}
    for states in worker_states[1:]:
        for p in persistent:
            combined[p.name] = p.combine_states(combined[p.name], states[p.name])
    presults = {p.name: p.synthesize(combined[p.name]) for p in persistent}
    mapper.end()
    return StreamResult(
        regions_processed=sum(counts),
        pixels_processed=sum(pixel_counts),
        persistent_results=presults,
        outputs=(
            [outputs_by_index[i] for i in sorted(outputs_by_index)]
            if outputs_by_index is not None
            else None
        ),
        cache_stats=cache.stats if use_jit else None,
    )


class BatchedRegionPuller:
    """Signature-batched region pulls: the serving engine's entry point into
    the ExecutionPlan layer.

    A batch of requested regions is described (cheap, per region), grouped by
    canonical plan signature — the :class:`PlanCache` key IS the batch key —
    and each group executes as **one** invocation of a ``jax.vmap``-batched
    build of the group's compiled plan: source arrays and origin scalars
    stack along a leading tile axis, so N same-signature tiles cost one XLA
    dispatch instead of N.  Batched programs register in the same
    :class:`PlanCache` under ``("serve_batched", signature, bucket)``; batch
    sizes round up to the configured buckets (padding replicates the last
    tile) so the registry holds a bounded number of batched traces per
    signature.  Outputs are bit-identical to the unbatched per-tile path —
    the serving-diff CI job locks this in.

    Pipelines with persistent filters are refused: a persistent reduction
    makes tile outputs depend on request order, which serving cannot honor.

    ``virtual`` should carry the same describe mode the streaming oracle
    would pick (:func:`_virtual_describe_mode`), so tile signatures collapse
    onto the entries a streaming warm-up run already lowered.

    ``read_cache_entries`` bounds an LRU of per-region source reads (the
    raster block cache of a tile server: hot Zipf tiles re-request the same
    windows, and the host-side read is the per-tile cost batching cannot
    amortize).  Cached reads are the *same arrays* the uncached path would
    produce, so outputs are unaffected; 0 disables.
    """

    def __init__(
        self,
        pipeline: Pipeline,
        node,
        plan_cache: Optional[PlanCache] = None,
        batch_sizes=(1, 4, 16),
        virtual: "Optional[bool | str]" = None,
        read_cache_entries: int = 1024,
    ):
        if pipeline.persistent_nodes():
            raise ValueError(
                "BatchedRegionPuller: pipeline has persistent filters "
                f"({[p.name for p in pipeline.persistent_nodes()]}) — "
                "per-tile serving cannot thread cross-region state"
            )
        self.pipeline = pipeline
        self.node = node
        self.plan_cache = plan_cache if plan_cache is not None else PlanCache()
        self.batch_sizes = tuple(sorted(set(int(b) for b in batch_sizes)))
        if not self.batch_sizes or self.batch_sizes[0] < 1:
            raise ValueError(f"bad batch_sizes: {batch_sizes}")
        self.virtual = (
            _virtual_describe_mode(pipeline) if virtual is None else virtual
        )
        self.read_cache_entries = int(read_cache_entries)
        self._read_cache: "collections.OrderedDict[Tuple, List]" = (
            collections.OrderedDict()
        )
        self._read_lock = threading.Lock()
        self.read_hits = 0
        self.read_misses = 0

    def _read(self, desc) -> List:
        """``desc.read_sources()`` through the bounded read LRU.  The key is
        the described output region + signature — for a fixed (pipeline,
        node, describe mode) that pins the exact read windows."""
        if self.read_cache_entries <= 0:
            return desc.read_sources()
        key = (desc.out_region.index, desc.out_region.size, desc.signature)
        with self._read_lock:
            arrays = self._read_cache.get(key)
            if arrays is not None:
                self._read_cache.move_to_end(key)
                self.read_hits += 1
                return arrays
        self.read_misses += 1
        arrays = desc.read_sources()
        with self._read_lock:
            self._read_cache[key] = arrays
            self._read_cache.move_to_end(key)
            while len(self._read_cache) > self.read_cache_entries:
                self._read_cache.popitem(last=False)
        return arrays

    def describe(self, region: ImageRegion):
        return self.pipeline.describe_pull(
            self.node, region, virtual=self.virtual
        )

    def _entry(self, desc) -> _CompiledEntry:
        return self.plan_cache.compiled_for(
            desc, lambda: self.pipeline.lower_pull(desc)
        )

    def bucket(self, n: int) -> int:
        """Smallest configured batch bucket holding ``n`` tiles (the largest
        bucket when ``n`` exceeds them all — callers split oversize groups)."""
        for b in self.batch_sizes:
            if n <= b:
                return b
        return self.batch_sizes[-1]

    def _batched_program(self, desc, bucket: int):
        """The jitted vmap of this signature's canonical closure, from the
        shared registry.  Mirrors ``_CompiledEntry``'s trace counting: the
        wrapper bumps ``stats.compiles`` at trace time only, so a warm
        registry proves itself with a zero compile delta."""
        entry = self._entry(desc)
        stats = self.plan_cache.stats

        def build():
            def counted(arrays, pstates, origins):
                stats.compiles += 1  # executes at trace time only
                return entry.canonical_fn(arrays, pstates, origins)

            return jax.jit(jax.vmap(counted, in_axes=(0, None, 0)))

        return self.plan_cache.get_or_build(
            ("serve_batched", desc.signature, bucket), build
        )

    def pull_one(self, region: ImageRegion) -> np.ndarray:
        """Unbatched single-region pull through the registry (the per-tile
        oracle the serving-diff compares the batched path against)."""
        desc = self.describe(region)
        out, _ = self._entry(desc).host(self._read(desc), {}, desc.origins())
        return out

    def _chunks(self, n: int) -> List[int]:
        """Decompose a group of ``n`` tiles into bucket-sized chunks, peeling
        exact smaller buckets off when padding to the next bucket would waste
        more than half the real work (8 tiles on buckets (1,4,16) runs as
        4+4, not padded to 16)."""
        out: List[int] = []
        while n > 0:
            b = self.bucket(n)
            if b <= n:
                take = b
            else:
                lower = max(x for x in self.batch_sizes if x <= n)
                if lower > 1 and (b - n) * 2 >= n:
                    take = lower
                else:
                    out.append(n)  # pad n up to b in a single call
                    break
            out.append(take)
            n -= take
        return out

    def pull_described(self, descs) -> List[np.ndarray]:
        """Execute already-described same-signature requests as one batched
        invocation (singletons skip the vmap program and run unbatched).
        Groups that don't land on a bucket split into bucket-exact chunks
        (see :meth:`_chunks`); only the final remainder pads."""
        if not descs:
            return []
        if len(descs) == 1:
            d = descs[0]
            out, _ = self._entry(d).host(self._read(d), {}, d.origins())
            return [out]
        sizes = self._chunks(len(descs))
        if len(sizes) > 1:
            out: List[np.ndarray] = []
            i = 0
            for s in sizes:
                out.extend(self.pull_described(descs[i : i + s]))
                i += s
            return out
        n = len(descs)
        bucket = self.bucket(n)
        arrays = [self._read(d) for d in descs]
        origins = [d.origins() for d in descs]
        while len(arrays) < bucket:  # pad by replicating the last tile
            arrays.append(arrays[-1])
            origins.append(origins[-1])
        stacked = [
            jnp.stack([a[k] for a in arrays]) for k in range(len(arrays[0]))
        ]
        ovecs = tuple(
            jnp.asarray([o[s] for o in origins], dtype=jnp.int32)
            for s in range(len(origins[0]))
        )
        fn = self._batched_program(descs[0], bucket)
        out, _ = fn(stacked, {}, ovecs)
        out = np.asarray(out)
        return [out[i] for i in range(n)]

    def pull_many(self, regions) -> List[np.ndarray]:
        """Pull a batch of regions, coalescing same-signature requests into
        one batched invocation each.  Output order matches input order."""
        descs = [self.describe(r) for r in regions]
        groups: Dict[Tuple, List[int]] = {}
        for i, d in enumerate(descs):
            groups.setdefault(d.signature, []).append(i)
        out: List[Optional[np.ndarray]] = [None] * len(regions)
        for idxs in groups.values():
            tiles = self.pull_described([descs[i] for i in idxs])
            for i, tile in zip(idxs, tiles):
                out[i] = tile
        return out  # type: ignore[return-value]

    def warm(self, regions, buckets=None) -> Dict[str, int]:
        """Serving warm-up: lower + compile every distinct signature in
        ``regions`` (executed once, via :meth:`PlanCache.warm`) and prime the
        vmap-batched programs for each requested bucket size (default: all
        configured buckets > 1), so the first live request after warm-up is
        a pure registry hit — zero lowers, zero compiles."""
        before = self.plan_cache.stats_snapshot()
        n_sigs = self.plan_cache.warm(
            self.pipeline, self.node, regions, virtual=self.virtual
        )
        buckets = tuple(
            b for b in (self.batch_sizes if buckets is None else buckets)
            if b > 1
        )
        seen = set()
        for region in regions:
            desc = self.describe(region)
            if desc.signature in seen:
                continue
            seen.add(desc.signature)
            for b in buckets:
                # prime with replicated copies of this region's real reads so
                # the jit traces (and XLA compiles) at the bucket shape now
                self.pull_described([desc] * b)
        after = self.plan_cache.stats_snapshot()
        return {
            "signatures": n_sigs,
            "buckets": len(buckets),
            **{f"{k}_delta": after[k] - before[k] for k in after},
        }


def execute(
    pipeline: Pipeline,
    mapper: Mapper,
    splitter: Optional[Splitter] = None,
    keep_outputs: bool = False,
    **executor_kw,
) -> StreamResult:
    """One-call convenience: stream the whole image through ``mapper``.

    ``keep_outputs`` is the run-time option; everything else in
    ``executor_kw`` goes to the :class:`StreamingExecutor` constructor."""
    executor = StreamingExecutor(pipeline, mapper, splitter, **executor_kw)
    return executor.run(keep_outputs=keep_outputs)
