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
     as traced arguments, ``fn(arrays, pstates, origins) -> (pixels,
     new_pstates)``.  Drifting warp requests become *windowed reads*
     (static-shape bounding windows, traced origins — see
     ``ProcessObject.window_bound``), so a striped warp run shares ONE
     signature across every stripe, borders included.
  2. **PlanCache** — the compiled-plan registry of the ExecutionPlan layer
     (:mod:`repro.core.execplan`), keyed by plan signature and shared with
     the SPMD :class:`~repro.core.parallel.ParallelExecutor`.  Border
     stripes describe against the virtual padded geometry where
     :meth:`Pipeline.virtual_describe_mode` allows it (the halo spill is
     edge-replicated at the read stage), so a uniform stripe split compiles
     exactly ONCE.  Registry hits run the describe pass only; the counts are
     in ``StreamResult.cache_stats``.
  3. **Async double buffering** — with ``prefetch=k``, the next ``k``
     regions are described and read on a thread pool while the device
     computes the current one, and ``mapper.consume`` runs on a write-behind
     thread behind a bounded queue.  In-flight memory stays bounded at
     roughly ``2·prefetch + 2`` region buffers (k read-ahead + one computing
     + k + 1 queued writes), preserving the paper's memory-budget guarantee
     with a constant factor.

Both host drivers run one per-region step (describe, gate, plan lookup and
read; launch, wait and pull to the host; consume) through one region loop.
:class:`StreamingExecutor` runs the loop once, on the calling thread, with
read-ahead and write-behind; ``run_pool`` runs it on ``n_workers`` threads at
read-ahead depth 0, draining one shared
:class:`~repro.core.scheduling.WorkStealingQueue` (or their static/LPT
slices) against a shared :class:`PlanCache` — the dynamic load balancing the
paper names as future work (§IV.C).  With ``use_jit=False`` the step is the
eager pull.
"""
from __future__ import annotations

import collections
import contextlib
import dataclasses
import functools
import queue
import threading
from concurrent.futures import ThreadPoolExecutor
from typing import Callable, Dict, Iterable, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.execplan import CacheStats, PlanCache, _CompiledEntry
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
    #: plan-cache counters for this run (None on the eager path).
    #: This is the LIVE CacheStats object — it keeps counting after the run
    #: (documented behavior, see ``reset_global_plan_cache``).
    cache_stats: Optional[CacheStats] = None


@dataclasses.dataclass
class _RegionStep:
    """The per-region step both host drivers run, in the parts the spans
    name.  :meth:`prepare` describes the region, waits on the region gate,
    looks its program up in the plan registry and reads its sources (the
    read-ahead half); :meth:`compute` launches the program, waits for it and
    pulls the pixels to the host.  With ``use_jit=False`` prepare only
    describes (and gates), and compute is the eager pull, whose hook
    accumulates into the worker's persistent states."""

    pipeline: Pipeline
    mapper: Mapper
    plan_cache: PlanCache
    use_jit: bool
    virtual: "bool | str"
    #: region-availability gate (pipelined stage DAGs): ``wait(desc)`` blocks
    #: until the rows the region reads are committed upstream, ``done(desc)``
    #: releases them once the region's output has been consumed
    region_gate: Optional[object] = None

    def prepare(self, region: ImageRegion):
        # describe pass only; the O(graph) closure tree is lowered by the
        # registry on misses — cache hits never rebuild it.  Virtual geometry
        # (when safe) folds border strips onto the interior signature.
        with span("describe", row0=region.row0, col0=region.col0):
            desc = self.pipeline.describe_pull(self.mapper, region, virtual=self.virtual)
        if self.region_gate is not None:
            self.region_gate.wait(desc)  # block until input rows commit
        if not self.use_jit:
            return region, desc, None, None
        fn = self.plan_cache.compiled_for(desc, lambda: self.pipeline.lower_pull(desc))
        with span("read", row0=region.row0, col0=region.col0) as sp:
            arrays = desc.read_sources()
            sp.set_metadata(bytes=sum(a.nbytes for a in arrays),
                            inputs=len(arrays))
        return region, desc, fn, arrays

    def compute(self, prep, pstates) -> Tuple[np.ndarray, Dict]:
        region, desc, fn, arrays = prep
        # the device's own time is busy, not idle: inside ``dispatch`` only
        # the program's launch and completion latency read as idle
        with span("dispatch", row0=region.row0, col0=region.col0):
            if fn is None:
                pstates = dict(pstates)

                def hook(node: PersistentFilter, reg: ImageRegion, inputs):
                    pstates[node.name] = node.accumulate(
                        pstates[node.name], reg, *inputs
                    )

                # eager pull; the hook observes every region exactly once
                out = self.pipeline.pull(self.mapper, region, persistent_hook=hook)
            else:
                out, pstates = fn.launch(arrays, pstates, desc.origins())
            jax.block_until_ready(out)
        with span("d2h", row0=region.row0, col0=region.col0) as sp:
            data = np.asarray(out) if fn is None else fn.to_host(out)
            sp.set_metadata(bytes=data.nbytes)
        return data, pstates


class _LoopResult(NamedTuple):
    regions: int
    pixels: int
    pstates: Dict
    #: region index -> pixels, when the outputs are kept
    kept: Dict[int, np.ndarray]


def _region_loop(
    step: _RegionStep,
    regions: List[ImageRegion],
    indices: Iterable[int],
    consume: Callable[[ImageRegion, np.ndarray], None],
    depth: int = 0,
    keep_outputs: bool = False,
) -> _LoopResult:
    """One worker's region loop, on the calling thread: ``step`` over
    ``regions[i]`` for each ``i`` that ``indices`` yields, with the worker's
    own persistent states.  At ``depth`` k > 0 the next k regions are
    prepared on read-ahead threads while the current one computes; at depth
    0 prepare runs inline.  ``consume(region, data)`` hands each region's
    pixels on (the mapper, or a write-behind queue); the region gate, if
    any, releases a region once it is consumed."""
    pstates = {p.name: p.reset() for p in step.pipeline.persistent_nodes()}
    kept: Dict[int, np.ndarray] = {}
    n = pixels = 0
    it = iter(indices)
    pending: "collections.deque" = collections.deque()
    # the pool starts no thread at depth 0: nothing is submitted to it
    with ThreadPoolExecutor(
        max_workers=max(1, depth), thread_name_prefix="prefetch"
    ) as pool:

        def fill(k: int) -> None:
            while len(pending) < k:
                i = next(it, None)
                if i is None:
                    return
                fut = pool.submit(step.prepare, regions[i]) if depth else None
                pending.append((i, fut))

        try:
            while True:
                fill(max(depth, 1))
                if not pending:
                    break
                i, fut = pending.popleft()
                region = regions[i]
                with span("wait_inputs", row0=region.row0, col0=region.col0):
                    prep = step.prepare(region) if fut is None else fut.result()
                fill(depth)  # keep the read window full while we compute
                data, pstates = step.compute(prep, pstates)
                with span("wait_write", row0=region.row0, col0=region.col0):
                    consume(region, data)
                if step.region_gate is not None:
                    step.region_gate.done(prep[1])  # release its input rows
                n += 1
                pixels += region.num_pixels
                if keep_outputs:
                    kept[i] = data
        finally:
            for _, fut in pending:
                if fut is not None:
                    fut.cancel()
    return _LoopResult(n, pixels, pstates, kept)


def _run_pass(
    step: _RegionStep,
    run_loops: Callable[[], List[_LoopResult]],
    keep_outputs: bool,
) -> StreamResult:
    """One pass over the image: ``mapper.begin``, the region loops
    ``run_loops`` runs (one result per worker), then the paper's Synthesis
    of the workers' combined persistent states and ``mapper.end``, which
    also runs when a loop fails (releasing writer descriptors)."""
    pipeline, mapper = step.pipeline, step.mapper
    info = pipeline.info(mapper)
    with span("begin"):
        mapper.begin(info)
    try:
        loops = run_loops()
    except BaseException:
        with contextlib.suppress(Exception):
            mapper.end()
        raise
    persistent = pipeline.persistent_nodes()
    states = loops[0].pstates
    for loop in loops[1:]:
        states = {
            p.name: p.combine_states(states[p.name], loop.pstates[p.name])
            for p in persistent
        }
    presults = {p.name: p.synthesize(states[p.name]) for p in persistent}
    with span("end"):
        mapper.end()
    kept = {i: data for loop in loops for i, data in loop.kept.items()}
    return StreamResult(
        regions_processed=sum(loop.regions for loop in loops),
        pixels_processed=sum(loop.pixels for loop in loops),
        persistent_results=presults,
        outputs=[kept[i] for i in sorted(kept)] if keep_outputs else None,
        cache_stats=step.plan_cache.stats if step.use_jit else None,
    )


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
        plan_cache: Optional[PlanCache] = None,
        prefetch: int = 2,
        max_cached_plans: Optional[int] = None,
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
        # explicit None check: an empty PlanCache is falsy (it has __len__)
        self.plan_cache = (
            plan_cache if plan_cache is not None else PlanCache(max_cached_plans)
        )
        self.prefetch = max(0, int(prefetch))
        # border strips describe against the virtual padded geometry (like
        # the SPMD prober) where the pipeline allows it, so a striped halo run
        # shares ONE interior signature
        self.describe_virtual = pipeline.virtual_describe_mode()

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

    def run(self, keep_outputs: bool = False) -> StreamResult:
        """The region loop once, on the calling thread: reads for the next
        ``prefetch`` regions overlap the device computing the current one,
        and writes trail behind on their own thread."""
        step = _RegionStep(
            self.pipeline, self.mapper, self.plan_cache, self.use_jit,
            self.describe_virtual,
        )

        def loop() -> List[_LoopResult]:
            regions = self.my_regions()
            # a best-effort hint: tiled/remote sources (RasterSource.read_ahead)
            # fetch the schedule's covering tiles on their own thread; plain
            # sources ignore it
            for src in self.pipeline.sources():
                ra = getattr(src, "read_ahead", None)
                if callable(ra):
                    ra(regions)
            order = range(len(regions))
            # read-ahead pays only over several regions, and the eager pull
            # has no read stage to run ahead
            depth = self.prefetch if self.use_jit and len(regions) > 1 else 0
            if not depth:
                return [_region_loop(step, regions, order, self.mapper.consume,
                                     0, keep_outputs)]
            writer = _WriteBehind(self.mapper.consume, depth + 1)
            try:
                return [_region_loop(step, regions, order, writer.put,
                                     depth, keep_outputs)]
            finally:
                with span("wait_write"):
                    writer.close()

        return _run_pass(step, loop, keep_outputs)


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
    """Run one pipeline with ``n_workers`` concurrent threads on this host,
    each running the region loop at read-ahead depth 0.

    With ``scheduler="work_stealing"`` the workers drain one shared
    :class:`WorkStealingQueue` (idle workers steal from the most-loaded
    victim's tail); ``"static"`` / ``"lpt"`` give each worker its precomputed
    slice.  All workers share one :class:`PlanCache`, so a uniform split
    still compiles once.  Per-worker persistent states are combined with the
    filters' reductions, then synthesized once — the thread-level analogue
    of the paper's MPI many-to-one Synthesis.

    ``region_gate`` (pipelined stage DAGs, :mod:`repro.core.dag`) makes the
    workers block *per region*: after a region's describe pass the gate
    waits until the input rows it reads are committed upstream, and it
    releases them once the region's output is consumed.  Gated runs, and
    runs with ``in_order=True`` (the pipelined orchestrator's producer
    stages), hand regions out in strict region order (:class:`FifoQueue`)
    whatever the ``scheduler``, so workers stay on the regions the commit
    frontier makes ready first and backpressure tracks that frontier."""
    if scheduler not in _SCHEDULERS:
        raise ValueError(scheduler)
    n_workers = max(1, int(n_workers))
    info = pipeline.info(mapper)  # also primes the metadata cache (thread-shared)
    splitter = splitter or StripeSplitter(n_splits=n_workers * 4)
    regions = splitter.split(info.full_region, info)
    cost = cost_fn or (lambda r: float(r.num_pixels))
    # the same border-strip virtualization as StreamingExecutor: all
    # workers land on the one interior signature (one lower + compile)
    step = _RegionStep(
        pipeline, mapper, plan_cache if plan_cache is not None else PlanCache(),
        use_jit, pipeline.virtual_describe_mode(), region_gate,
    )
    lock = None if getattr(mapper, "thread_safe", False) else threading.Lock()

    def consume(region: ImageRegion, data: np.ndarray) -> None:
        with lock or contextlib.nullcontext():
            mapper.consume(region, data)

    if region_gate is not None or in_order:
        shared = FifoQueue(len(regions))
    elif scheduler == "work_stealing":
        shared = WorkStealingQueue(
            len(regions), n_workers, costs=[cost(r) for r in regions]
        )
    else:
        shared = None
        sched = (
            static_schedule(regions, n_workers)
            if scheduler == "static"
            else lpt_schedule(regions, n_workers, cost)
        )

    def work(w: int) -> _LoopResult:
        indices = (sched[w] if shared is None
                   else iter(functools.partial(shared.take, w), None))
        return _region_loop(step, regions, indices, consume,
                            keep_outputs=keep_outputs)

    def loops() -> List[_LoopResult]:
        if n_workers == 1:
            return [work(0)]
        with ThreadPoolExecutor(
            max_workers=n_workers, thread_name_prefix="pool"
        ) as pool:
            futs = [pool.submit(work, w) for w in range(n_workers)]
            return [f.result() for f in futs]

    return _run_pass(step, loops, keep_outputs)


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
    would pick (:meth:`Pipeline.virtual_describe_mode`), so tile signatures collapse
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
            pipeline.virtual_describe_mode() if virtual is None else virtual
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
