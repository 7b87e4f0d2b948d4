"""The ExecutionPlan layer: one compiled-plan registry for every executor.

The paper's framework promises that *any* pipeline runs on *any* cluster
layout transparently; the executors must therefore agree on what a compiled
plan *is*.  This module owns that contract:

  * :class:`PlanDescription` — the result of the cheap *describe* pass
    (``Pipeline.describe_pull``): the set of source reads, the canonical plan
    signature, the dynamic origin scalars and the persistent nodes for one
    (node, region) request.  Building it costs one host-side graph walk and
    **no** closure construction — it is run once per region, on every region.
  * :class:`PlanCache` — the process-shareable compiled-plan registry, keyed
    by canonical signature.  The *lower* pass (``Pipeline.lower_pull``, which
    builds the jittable closure tree) runs only on registry misses; hits are
    describe-pass-only.  Both :class:`~repro.core.streaming.StreamingExecutor`
    and :class:`~repro.core.parallel.ParallelExecutor` consult one registry,
    so a pipeline traced by one executor is a cache *hit* for the other on
    matching strip geometry.
  * :func:`global_plan_cache` — the process-wide default registry
    (LRU-bounded), used by the orchestrator so stages mixing streaming and
    SPMD workers share compiled plans.

Plan signatures embed per-node *serial numbers* (monotonic construction
counters, see :class:`~repro.core.process_object.ProcessObject`) rather than
``id()`` values, so a process-wide registry can never confuse a dead
pipeline's recycled object ids with a live one's.

Plan lifecycle — every executor follows the same steps::

      (node, region)
            │ tile-grid probe   SPMD only: build_tile_plan describes EVERY
            ▼                   virtual tile of the nr × nc padded grid
      virtual tile geometry     (virtual_tile_regions; 1-D strips are the
            │                   nc = 1 column) and demands one shared
            │                   interior signature — else
            │                   NotTileParallelizable with diagnostics
            │ describe          Pipeline.describe_pull — one host graph walk:
            ▼                   exact requests of needs_origin nodes become
      PlanDescription           static-shape WINDOW specs (window_bound hook);
            │                   reads/origins recorded, no closures built
            │ fuse              the SAME walk classifies the Pallas fast
            ▼                   path: pallas_plan() nodes become "pallas"
      fusion classification     steps and single-consumer pointwise chains
            │                   feeding them (pointwise_fn) FOLD into the
            │ signature         kernel — fused nodes leave no records
            ▼
      canonical signature       shape/pad/plan-key statics + node serials +
            │ registry lookup   window-spec shapes + pallas/fusion records;
            ▼                   absolute coordinates and window origins stay
      PlanCache.compiled_for    OUT (traced scalars)
            │         │
            │         └── hit ──► _CompiledEntry (reuse, zero lowers)
            ▼ miss
      lower                     Pipeline.lower_pull — closure tree; pallas
            │                   steps lower to pallas_body(pre_fns): ONE
            ▼                   fused Pallas call per tile, the chain's
      PullPlan.canonical_fn     pre_fns applied on VMEM tiles in-kernel
            │                   fn(arrays, pstates, origins) → jit + register
            │ tiled read        read_plan_sources resolves every plan read
            ▼                   through the Source/Sink protocol: flat RTIF
      source arrays             memmap windows, or RTIC tiled reads (tile
                                cover ∩ LRU cache over range requests, with
                                the streaming engine's schedule prefetched
                                async via RasterSource.read_ahead).  Tiled
                                sources stamp tile geometry + overview level
                                into the read records (Source.read_record),
                                so a re-tiled container never aliases a flat
                                source's signature — and a TiledSource plan
                                warmed by one executor is a registry hit for
                                every other, same as flat sources.

Serving request path — the tile-serving front end (:mod:`repro.serve.tiles`)
rides the same lifecycle, one extra registry hop deep::

      TileRequest (pipeline, zoom, x, y)
            │ admission         serve.admission — bounded queue depth,
            ▼                   shed-or-block policy
      (node, tile region)       TileGrid.region(x, y)
            │ describe          the SAME describe pass as above — the
            ▼                   plan signature IS the batch key
      signature group           concurrent requests with equal signatures
            │ batch             coalesce into ONE invocation: arrays and
            ▼                   origin scalars stack along a leading tile
      batched program           axis, jax.vmap(canonical_fn) jits under
            │                   ("serve_batched", signature, bucket) via
            ▼                   get_or_build — post warm-up every hop is
      (tiles, no new traces)    a registry hit: zero lowers, zero compiles

:meth:`PlanCache.warm` is the warm-up protocol: describe a geometry sweep,
lower every distinct signature, and (``execute=True``) run each entry once so
XLA traces before the first live request.  :meth:`PlanCache.stats_snapshot`
freezes the counters as a plain dict — the serving metrics and the perf
benches diff two snapshots instead of reaching into live counters.

Windowed reads make this lifecycle *total* over P1–P7: a warp's drifting
request is classified at describe time as a conservative static bounding
window (rows anchored at the request origin, columns shifted in-image), so
interior regions of one size share one signature, the streaming engine
prefetches fixed-shape windows, and the SPMD executor lowers the same entry
to ``lax.dynamic_slice`` of the halo-exchanged shard — one trace per
geometry signature on every engine.

Virtual padded tiles make it total over *arbitrary tile-grid geometry*: the
describe pass can run against a virtually padded image
(``describe_pull(..., virtual=True)`` — the ``"grid"`` mode, no clamping in
either axis; ``virtual="rows"`` keeps the restricted rows-only walk for
pipelines whose column borders are not virtualization-safe), so the ragged
edge tiles of an uneven ``nr × nc`` split — and both border strips of an
n=2 halo split — describe exactly like interior tiles and *share the
interior signature*.  The resulting :class:`PlanDescription` carries the pad
metadata (the ``virtual`` mode + ``pad_rows``/``pad_cols``, the trailing
output rows/cols beyond the real image) OUTSIDE the signature: registry
lookup still lands on the one interior entry, the read stage materializes
the spilled rows/cols by edge replication (:func:`read_plan_sources`
host-side, halo replication of the edge-padded global under SPMD),
mask-aware persistent filters accumulate under an in-trace 2-D validity mask
derived from their traced (row, col) origin, and the executor crops the pad
before the write stage.
"""
from __future__ import annotations

import collections
import dataclasses
import math
import threading
from typing import TYPE_CHECKING, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.process_object import boundary_pad
from repro.core.region import ImageRegion

if TYPE_CHECKING:  # pragma: no cover — typing only, avoids an import cycle
    from repro.core.pipeline import PullPlan
    from repro.core.process_object import PersistentFilter, ProcessObject, Source


@dataclasses.dataclass
class CacheStats:
    """Counters for one :class:`PlanCache`.

    ``compiles`` counts actual jax traces of registry entries (incremented
    from inside the traced body, so a value of 1 proves a whole run retraced
    exactly once).  ``lowers`` counts closure-tree constructions (lower
    passes) — on the describe-pass path a cache hit performs zero of either.
    """

    compiles: int = 0
    hits: int = 0
    misses: int = 0
    evictions: int = 0
    lowers: int = 0

    def snapshot(self) -> Dict[str, int]:
        """The counters frozen as a plain dict — the live object keeps
        counting, the snapshot does not.  Consumers that need a before/after
        delta (serving metrics, bench gates) diff two snapshots instead of
        caching references into live counters."""
        return {
            "compiles": self.compiles,
            "hits": self.hits,
            "misses": self.misses,
            "evictions": self.evictions,
            "lowers": self.lowers,
        }


def read_plan_sources(reads, windows) -> List:
    """Materialize a plan's source reads (shared by :class:`PlanDescription`
    and :class:`~repro.core.pipeline.PullPlan`).  Windowed reads are
    delivered at the full static window shape — the trace carries no pads
    for them, so border spill is edge-replicated here, at the read stage.

    The read stage is *total over virtual geometry* in both axes: a read
    whose region spills past the source's real rows **or columns** (virtual
    padded tiles) is clamped to the image and edge-replicated back out — the
    host-side twin of the SPMD executor's edge-padded-global + row/column
    halo replication, so a virtual plan's inputs carry the same pixel values
    on every engine.

    An empty ``windows`` means "no windowed reads" (plans built before the
    describe pass existed); a non-empty tuple must align with ``reads``.
    """
    if windows and len(windows) != len(reads):
        raise ValueError(
            f"windows/reads misaligned: {len(windows)} window specs for "
            f"{len(reads)} reads"
        )
    def snap(lo: int, hi: int, n: int):
        """Per axis: the in-image read range and the edge pads placing it
        back inside half-open [lo, hi).  The range is the overlap with
        [0, n) when one exists; on a fully-virtual axis it is the nearest
        single edge unit and every output unit replicates it (one-sided pad
        — the single source value makes the split immaterial)."""
        a, b = max(lo, 0), min(hi, n)
        if a < b:
            return a, b, (a - lo, hi - b)
        if hi <= 0:  # entirely above/left of the image: replicate unit 0
            return 0, 1, ((hi - lo) - 1, 0)
        return n - 1, n, (0, (hi - lo) - 1)  # entirely below/right

    wins = windows if windows else (None,) * len(reads)
    out = []
    for (s, clamped, region), w in zip(reads, wins):
        full = s.output_info().full_region
        have = clamped.clamp(full)
        if not have.is_empty():
            arr = boundary_pad(s.generate(have), have, clamped)
        else:
            # the region misses the image entirely on >= 1 axis (a strip
            # fully past the border, e.g. more workers than rows): read the
            # nearest edge unit on the virtual axis and replicate outward —
            # pure edge extension, the exact values the SPMD padded global
            # holds over its pad rows
            r0, r1, rpad = snap(clamped.row0, clamped.row1, full.rows)
            c0, c1, cpad = snap(clamped.col0, clamped.col1, full.cols)
            arr = np.asarray(s.generate(ImageRegion((r0, c0), (r1 - r0, c1 - c0))))
            arr = np.pad(
                arr, [rpad, cpad] + [(0, 0)] * (arr.ndim - 2), mode="edge"
            )
        if w is not None:
            arr = boundary_pad(arr, clamped, region)
        out.append(arr)
    return out


@dataclasses.dataclass
class PlanDescription:
    """Output of the describe pass: everything the registry and the read
    stage need, with no compiled closure attached.

    ``reads``: list of (source, clamped_region, requested_region) in plan
    order; ``signature`` is the canonical plan key (shape/boundary/plan-key
    static data, per-node serials); ``origin_values`` are this region's
    absolute coordinates for ``needs_origin`` nodes — and the absolute row
    origins of mask-aware persistent filters — threaded into the compiled
    function as traced scalars.  ``windows[i]`` is the static (rows, cols)
    window-spec shape when read *i* is a windowed read (the request of a
    ``needs_origin`` node lowered to a fixed-shape bounding window whose
    origin is traced), else None.

    Pad metadata: ``virtual`` carries the virtual-describe mode the walk ran
    in (``False`` for the exact walk, ``"grid"`` for the fully unclamped 2-D
    walk, ``"rows"`` for the restricted rows-only walk — a tile spilling
    past the image shares the interior signature), and ``pad_rows`` /
    ``pad_cols`` count the trailing output rows/cols that lie beyond the
    real image (0 on real geometry).  None of these is part of the
    signature — that is the point: a virtual tile's plan *is* the interior
    plan, and the executor crops/masks the pad instead.
    """

    node: "ProcessObject"
    out_region: "ImageRegion"
    reads: List[Tuple["Source", "ImageRegion", "ImageRegion"]]
    signature: Tuple
    origin_values: Tuple[int, ...]
    persistent_nodes: List["PersistentFilter"]
    windows: Tuple[Optional[Tuple[int, int]], ...] = ()
    virtual: "bool | str" = False
    pad_rows: int = 0
    pad_cols: int = 0
    #: serials of nodes the plan lowers to fused Pallas bodies, and of the
    #: pointwise nodes folded into one — diagnostic mirrors of the
    #: signature's ``("pallas", ...)`` records (empty on jnp-only plans)
    pallas_nodes: Tuple[int, ...] = ()
    fused_nodes: Tuple[int, ...] = ()

    def read_sources(self) -> List:
        return read_plan_sources(self.reads, self.windows)

    def origins(self) -> Tuple[np.int32, ...]:
        """Per-region dynamic origin scalars, in canonical slot order.  Passed
        as arrays so jit traces (not bakes) them."""
        return tuple(np.int32(v) for v in self.origin_values)

    def initial_pstates(self) -> Dict[str, Dict]:
        return {p.name: p.reset() for p in self.persistent_nodes}


#: lanes in a TPU vector register
_LANES = 128


def _lane_dense(out):
    """A ``(rows, cols, ...)`` pixel output as a 2-D ``(rows, cols' *
    pixel)`` array of whole pixels, ``cols' >= cols`` the fewest columns
    whose row fills whole 128-lane vectors (the added columns are zeros).
    A TPU lays such an output out row-major, so it reaches the host
    C-ordered row by row; a rank-3 strip, or a 2-D one whose row ends
    inside a vector, it lays out rows fastest.  Padding the columns costs
    the compiler nothing; relayouting the strip to one flat row instead
    takes it seconds to minutes a strip shape."""
    pixel = math.prod(out.shape[2:])
    pad = -out.shape[1] % (_LANES // math.gcd(pixel, _LANES))
    if pad:
        out = jnp.pad(out, [(0, 0), (0, pad)] + [(0, 0)] * (out.ndim - 2))
    return out.reshape(out.shape[0], -1)


class _CompiledEntry:
    """One jitted canonical function.  The first call is serialized so
    concurrent pool workers can't race XLA into tracing the same signature
    twice; afterwards calls are lock-free.  ``canonical_fn`` stays reachable
    so the SPMD executor can trace the very same closure into its shard_map
    program instead of rebuilding it.

    The program hands its pixel output back lane-dense (see
    :func:`_lane_dense`), pixel-interleaved in row-major order;
    :meth:`to_host` views the host array back to the canonical output's
    shape at no cost, each row C-ordered, rows a padded row apart."""

    def __init__(self, canonical_fn: Callable, stats: CacheStats):
        self.canonical_fn = canonical_fn
        #: lane-dense output shape -> the canonical output shape it holds
        self._views: Dict[Tuple[int, ...], Tuple[int, ...]] = {}

        def lane_dense(arrays, pstates, origins):
            stats.compiles += 1  # executes at trace time only
            out, new = canonical_fn(arrays, pstates, origins)
            if out.ndim > 1:
                dense = _lane_dense(out)
                if dense.shape != out.shape:
                    self._views[dense.shape] = out.shape
                out = dense
            return out, new

        self._jitted = jax.jit(lane_dense)
        self._lock = threading.Lock()
        self._primed = False

    def launch(self, arrays, pstates, origins):
        """Enqueue the program: its lane-dense pixel output, on the device,
        and the new persistent states."""
        if not self._primed:
            with self._lock:
                out = self._jitted(arrays, pstates, origins)
                self._primed = True
                return out
        return self._jitted(arrays, pstates, origins)

    def to_host(self, out) -> np.ndarray:
        """A launched pixel output on the host, in the canonical output's
        shape: a view of the lane-dense array without its padding."""
        host = np.asarray(out)
        shape = self._views.get(host.shape)
        if shape is None:
            return host
        return host[:, : math.prod(shape[1:])].reshape(shape)

    def host(self, arrays, pstates, origins) -> Tuple[np.ndarray, Dict]:
        """Run the program: the pixels as :meth:`to_host` gives them, and
        the new persistent states."""
        out, pstates = self.launch(arrays, pstates, origins)
        return self.to_host(out), pstates


class PlanCache:
    """Compiled-plan registry keyed by canonical plan signature.

    Shareable across executors / pool workers / orchestrator stages (all
    methods are thread-safe).  ``max_entries`` bounds the registry with LRU
    eviction; evicted entries recompile on next use (counted in stats).

    Besides per-region pull plans the registry also holds whole executor
    programs (e.g. a jitted shard_map SPMD program) via :meth:`get_or_build`,
    so repeated :class:`~repro.core.parallel.ParallelExecutor` runs on the
    same pipeline/geometry reuse one program.
    """

    def __init__(self, max_entries: Optional[int] = None):
        if max_entries is not None and max_entries < 1:
            raise ValueError("max_entries must be >= 1")
        self.max_entries = max_entries
        self.stats = CacheStats()
        self._entries: "collections.OrderedDict[Tuple, object]" = (
            collections.OrderedDict()
        )
        self._lock = threading.Lock()

    def __len__(self) -> int:
        return len(self._entries)

    def _store(self, key, value):
        self._entries[key] = value
        if self.max_entries is not None and len(self._entries) > self.max_entries:
            self._entries.popitem(last=False)
            self.stats.evictions += 1

    def compiled(self, plan: "PullPlan") -> _CompiledEntry:
        """The compiled function for an already-lowered ``plan`` (the legacy
        entry point: the caller paid the closure build regardless of hit or
        miss).  Plans with equal signatures share one entry."""
        key = plan.signature
        with self._lock:
            entry = self._entries.get(key)
            if entry is not None:
                self.stats.hits += 1
                self._entries.move_to_end(key)
                return entry
            self.stats.misses += 1
            self.stats.lowers += 1  # the caller lowered eagerly for this miss
            entry = _CompiledEntry(plan.canonical_fn, self.stats)
            self._store(key, entry)
            return entry

    def compiled_for(
        self, desc: PlanDescription, lower: Callable[[], "PullPlan"]
    ) -> _CompiledEntry:
        """The compiled function for ``desc``'s signature.  On a hit the
        closure tree is **not** rebuilt — ``lower`` runs only on misses, and
        *outside* the registry lock so a miss never stalls other workers'
        hits (two workers racing the same cold signature may both lower; the
        first insert wins and only it is counted — XLA tracing is still
        deduplicated by the entry's own priming lock)."""
        key = desc.signature
        with self._lock:
            entry = self._entries.get(key)
            if entry is not None:
                self.stats.hits += 1
                self._entries.move_to_end(key)
                return entry
        plan = lower()
        with self._lock:
            entry = self._entries.get(key)
            if entry is not None:  # lost the race: the peer's lower won
                self.stats.hits += 1
                self._entries.move_to_end(key)
                return entry
            self.stats.misses += 1
            self.stats.lowers += 1
            entry = _CompiledEntry(plan.canonical_fn, self.stats)
            self._store(key, entry)
            return entry

    def stats_snapshot(self) -> Dict[str, int]:
        """The registry counters as a plain dict (see
        :meth:`CacheStats.snapshot`).  This is the supported way to read the
        counters for metrics/benchmarks; the serving engine's ``metrics()``
        surfaces exactly this."""
        return self.stats.snapshot()

    def warm(
        self,
        pipeline,
        node,
        regions,
        virtual: "bool | str" = False,
        execute: bool = True,
    ) -> int:
        """Warm-up protocol: describe every region of a geometry sweep, lower
        each *distinct* signature into the registry, and (``execute=True``)
        run each entry once so XLA traces now rather than on the first live
        request.  Returns the number of distinct signatures ensured.

        ``pipeline``/``node`` follow the ``Pipeline.describe_pull`` protocol;
        ``virtual`` selects the virtually padded describe walk (``"grid"`` /
        ``"rows"`` / ``False`` — callers should pass
        ``Pipeline.virtual_describe_mode()``, the same mode their
        serving/streaming path will use, or the warmed signatures won't be
        the ones the live path looks up).
        """
        seen = set()
        for region in regions:
            desc = pipeline.describe_pull(node, region, virtual=virtual)
            if desc.signature in seen:
                continue
            seen.add(desc.signature)
            entry = self.compiled_for(desc, lambda: pipeline.lower_pull(desc))
            if execute:
                out, _ = entry.launch(
                    desc.read_sources(), desc.initial_pstates(), desc.origins()
                )
                jax.block_until_ready(out)
        return len(seen)

    def get_or_build(self, key: Tuple, build: Callable[[], object]):
        """Generic registry slot for executor-level programs (keyed by the
        caller; e.g. a jitted SPMD program under its geometry signature).
        ``build`` runs outside the lock; the first insert wins."""
        with self._lock:
            entry = self._entries.get(key)
            if entry is not None:
                self.stats.hits += 1
                self._entries.move_to_end(key)
                return entry
        built = build()
        with self._lock:
            entry = self._entries.get(key)
            if entry is not None:
                self.stats.hits += 1
                self._entries.move_to_end(key)
                return entry
            self.stats.misses += 1
            self._store(key, built)
            return built


_GLOBAL_LOCK = threading.Lock()
_GLOBAL_CACHE: Optional[PlanCache] = None


def global_plan_cache() -> PlanCache:
    """The process-wide compiled-plan registry (LRU-bounded).

    Executors accept any :class:`PlanCache`; this is the canonical shared one
    — the orchestrator and :func:`repro.pipelines.run_pipeline` default to
    it, so streaming, pool and SPMD runs in one process share compiled plans.
    """
    global _GLOBAL_CACHE
    with _GLOBAL_LOCK:
        if _GLOBAL_CACHE is None:
            _GLOBAL_CACHE = PlanCache(max_entries=512)
        return _GLOBAL_CACHE


def reset_global_plan_cache() -> PlanCache:
    """Swap in a fresh process-wide registry and return the **old** one.

    The old cache object (and its :class:`CacheStats`) stays fully usable:
    executors that captured it — e.g. a ``StreamResult.cache_stats`` from an
    earlier run — keep reading their own counters (evictions included), so a
    reset never zeroes history out from under a caller.  Subsequent
    :func:`global_plan_cache` calls see an empty registry with fresh
    counters."""
    global _GLOBAL_CACHE
    with _GLOBAL_LOCK:
        old = _GLOBAL_CACHE if _GLOBAL_CACHE is not None else PlanCache(max_entries=512)
        _GLOBAL_CACHE = PlanCache(max_entries=512)
        return old
