"""Pipeline DAG + the three-phase pull protocol (paper §II.B).

``Pipeline`` wires process objects into a directed graph and implements:

  * ``update_information()``   — phase 1, metadata downstream;
  * ``pull(node, region)``     — phases 2+3 for one requested region (eager);
  * ``describe_pull(node, region)`` — the cheap *describe* pass: source
    reads, canonical plan signature and origin scalars, with no closure
    construction.  Run once per region; on a plan-registry hit it is the
    only per-region graph work.
  * ``compile_pull(node, region)`` — describe **plus** the *lower* pass: the
    pure jax closure mapping source arrays → output pixels.  This is what
    the shard_map parallel driver partitions, and what ``jax.jit`` compiles
    for the streaming driver's hot loop.  ``lower_pull(desc)`` lowers an
    existing description (the :class:`~repro.core.execplan.PlanCache` calls
    it on registry misses only).

Plans are *canonical*: every region-dependent quantity that XLA must treat as
static (array shapes, boundary-pad widths, graph structure) is folded into
``PullPlan.signature``, while absolute coordinates consumed by
``needs_origin`` filters are threaded through the compiled function as traced
scalar arguments.  Two regions with equal signatures (e.g. all interior
stripes of a uniform split) can therefore share one compiled function — the
streaming engine's :class:`~repro.core.streaming.PlanCache` keys on exactly
this.  Persistent-filter state is threaded through the pure function
(``fn(arrays, pstates, origins) -> (pixels, new_pstates)``), so pipelines
containing :class:`PersistentFilter` nodes compile instead of falling back to
the eager pull.

Border semantics: at *every* producer→consumer edge, the consumer's request is
clamped against the producer's largest possible region and edge-replicated
back out (ITK boundary condition), so requests may safely spill over borders.

Windowed reads: requests made by ``needs_origin`` nodes drift fractionally
with the output origin, which would give every region its own signature.
When the node declares :meth:`ProcessObject.window_bound`, every pass (eager
pull, describe, lower) replaces the exact request with a conservative
static-shape bounding window (``process_object.window_request``) whose
absolute origin is a traced scalar, so all regions of one size share a
single trace.  Windowed reads carry no boundary pads in the trace — border
spill is edge-replicated at the read stage — so border regions share the
interior signature too.

Virtual padded tiles: ``describe_pull(..., virtual=...)`` runs the same walk
against a *virtually padded* geometry.  Two modes exist: ``"grid"`` (the
default for ``virtual=True``) never clamps in **either** axis, so a tile of
a 2-D SPMD grid that spills past the real image rows *or* columns describes
exactly like an interior tile and shares the interior plan signature;
``"rows"`` is the restricted legacy mode (rows unclamped, columns clamp
in-image) for pipelines whose column borders are not virtualization-safe
(:meth:`Pipeline.virtual_cols_safe`).  Spilled rows/cols are materialized at
the read stage instead (edge-replicated halos under SPMD,
:func:`~repro.core.execplan.read_plan_sources`'s clamp+pad host-side).
Mask-aware persistent filters (``supports_mask``) always thread their output
region's absolute (row, col) origin through the plan as traced scalars and
accumulate under an in-trace 2-D validity mask (pixels inside the real
image), so the masked-persistent case runs through the very same registry
body — with an all-true mask on real geometry and pad rows/cols masked out
on virtual geometry.  :meth:`Pipeline.virtual_describe_mode` picks the
strongest safe mode per pipeline; the describe caller (streaming warm-up,
SPMD tile prober) must use the same mode so both land on one registry entry.

Pallas fast path: a node whose ``pallas_plan()`` hook is true lowers to the
fused kernel body from ``pallas_body()`` instead of its ``generate`` — and
single-consumer runs of pointwise nodes feeding it (``pointwise_fn`` hook:
dtype converts, band math, quantize-style rescales) fold INTO that body, so
a registry hit executes one fused Pallas call per strip instead of N jnp
passes with materialized HBM intermediates.  The fusion decision uses graph
structure and static node state only, is made identically by the describe
and the lower walk, and is recorded in the plan signature as a ``"pallas"``
step (kernel serial + the fused chain's serials), so Pallas and jnp plans of
one graph never collide in the registry and a warm registry sees zero new
lowers/compiles.  Fused chain nodes contribute no signature records of their
own — their pixels exist only inside the kernel's VMEM tiles.  Fusion
refuses (and the plan falls back to plain node records) for multi-consumer,
multi-input, origin-aware, persistent, plan-keyed or grid-changing nodes —
exactly the nodes whose pixels or state must stay observable.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax.numpy as jnp
import numpy as np

from repro.core.execplan import PlanDescription, read_plan_sources
from repro.core.process_object import (
    ImageInfo,
    Mapper,
    PersistentFilter,
    ProcessObject,
    Source,
    boundary_pad,
    windowed_requests,
)
from repro.core.region import ImageRegion


def _normalize_virtual(virtual) -> "bool | str":
    """Canonical virtual-describe mode: ``False`` (exact walk), ``"rows"``
    (rows unclamped, columns clamp in-image) or ``"grid"`` (neither axis
    clamps).  ``True`` means the full 2-D mode — the 1-D strip path is the
    ``nc = 1`` column of the grid, not a separate dialect."""
    if virtual is False or virtual is None:
        return False
    if virtual is True or virtual == "grid":
        return "grid"
    if virtual == "rows":
        return "rows"
    raise ValueError(f"unknown virtual describe mode: {virtual!r}")


class Pipeline:
    def __init__(self):
        self._inputs: Dict[int, List[ProcessObject]] = {}
        self._nodes: List[ProcessObject] = []
        self._infos: Optional[Dict[int, ImageInfo]] = None

    # -- graph construction --------------------------------------------------
    def add(self, obj: ProcessObject, inputs: Sequence[ProcessObject] = ()) -> ProcessObject:
        if len(inputs) != obj.n_inputs:
            raise ValueError(
                f"{obj.name}: expected {obj.n_inputs} inputs, got {len(inputs)}"
            )
        for up in inputs:
            if id(up) not in self._inputs:
                raise ValueError(f"{obj.name}: input {up.name} not in pipeline")
        self._nodes.append(obj)
        self._inputs[id(obj)] = list(inputs)
        self._infos = None  # invalidate
        return obj

    def inputs_of(self, obj: ProcessObject) -> List[ProcessObject]:
        return self._inputs[id(obj)]

    @property
    def nodes(self) -> List[ProcessObject]:
        return list(self._nodes)

    def sources(self) -> List[Source]:
        return [n for n in self._nodes if isinstance(n, Source)]

    def mappers(self) -> List[Mapper]:
        return [n for n in self._nodes if isinstance(n, Mapper)]

    def persistent_nodes(self) -> List[PersistentFilter]:
        return [n for n in self._nodes if isinstance(n, PersistentFilter)]

    def virtual_rows_safe(self) -> bool:
        """True when virtual (unclamped-row) describes cannot change pixels.

        The two walk modes agree exactly when every request that can spill
        past an image's row extent lands on a **source** (the read stage
        materializes the spill by edge replication either way) — possibly
        through *row-transparent* filters, whose requests are row-identity:
        a streamable row-identity filter is row-local, so edge replication
        commutes through it (``replicate(f(x)) == f(replicate(x))``), and on
        the Pallas path fused pointwise chains compute over the padded source
        read outright.  The unsafe shape is spilled rows reaching a
        row-*stencil* intermediate — the exact walk clamps there and
        edge-replicates that filter's OUTPUT rows, while the virtual walk
        computes the spilled rows from edge-replicated SOURCE pixels.  For
        stacked neighborhood filters (e.g. smoothing → gradient) those
        conventions produce genuinely different border rows, so such
        pipelines must keep exact describes.

        The probe is structural (each consumer's requests over top and
        bottom border strips of its own grid, graph + static node state
        only), so every describe/lower pair classifies identically.
        """
        return self._virtual_axis_safe("rows")

    def virtual_cols_safe(self) -> bool:
        """True when virtual (unclamped-column) describes cannot change
        pixels — the column mirror of :meth:`virtual_rows_safe`: spill past
        an image's column extent must reach sources only, possibly through
        column-transparent (column-identity-request) filters.  Both axes
        safe ⇒ ``"grid"`` describes are exact; see
        :meth:`virtual_describe_mode`."""
        return self._virtual_axis_safe("cols")

    def virtual_describe_mode(self) -> "bool | str":
        """The strongest virtual describe mode this pipeline supports:
        ``"grid"`` (neither axis clamps — required by 2-D tile-grid SPMD),
        ``"rows"`` (rows-only virtualization), or ``False`` (exact describes
        only).  Requires every persistent filter to be mask-aware — an
        unmaskable accumulator would double-count edge-replicated pad pixels.
        Every describe producer for one pipeline (streaming warm-up, the
        SPMD tile prober, the serving engine) must take its mode from here,
        or warm-up and execution would land on different registry entries."""
        if not all(p.supports_mask for p in self.persistent_nodes()):
            return False
        if not self._virtual_axis_safe("rows"):
            return False
        return "grid" if self._virtual_axis_safe("cols") else "rows"

    def _virtual_axis_safe(self, axis: str) -> bool:
        """Shared structural probe behind :meth:`virtual_rows_safe` /
        :meth:`virtual_cols_safe` — identical propagation logic, border
        probes and identity checks taken along ``axis``."""
        infos = self.update_information()
        on_rows = axis == "rows"

        def lo(r: ImageRegion) -> int:
            return r.row0 if on_rows else r.col0

        def hi(r: ImageRegion) -> int:
            return r.row1 if on_rows else r.col1

        def extent(info: ImageInfo) -> int:
            return info.rows if on_rows else info.cols

        probes_of = {}  # id(n) -> pair of border probe regions on `axis`
        reqs_of = {}  # id(n) -> per-probe request tuples
        for n in self._nodes:
            ups = self._inputs[id(n)]
            if not ups:
                continue
            own = infos[id(n)]
            in_infos = [infos[id(u)] for u in ups]
            if on_rows:
                pr = max(1, min(own.rows, 8))
                probes = (
                    ImageRegion((0, 0), (pr, own.cols)),
                    ImageRegion((own.rows - pr, 0), (pr, own.cols)),
                )
            else:
                pc = max(1, min(own.cols, 8))
                probes = (
                    ImageRegion((0, 0), (own.rows, pc)),
                    ImageRegion((0, own.cols - pc), (own.rows, pc)),
                )
            probes_of[id(n)] = probes
            reqs_of[id(n)] = tuple(
                n.requested_region(probe, *in_infos) for probe in probes
            )

        def transparent(u) -> bool:
            # every request of u is axis-identity with its probe region
            if id(u) not in reqs_of:
                return False  # sources handled by the caller
            return all(
                lo(req) == lo(probe) and hi(req) == hi(probe)
                for probe, reqs in zip(probes_of[id(u)], reqs_of[id(u)])
                for req in reqs
            )

        # propagate "may receive out-of-image rows/cols" consumer→producer
        # (insertion order is topological, so reverse order visits every
        # consumer before its producers)
        spilled = set()
        for n in reversed(self._nodes):
            ups = self._inputs[id(n)]
            if not ups:
                continue
            in_infos = [infos[id(u)] for u in ups]
            for probe, reqs in zip(probes_of[id(n)], reqs_of[id(n)]):
                for u, upi, req in zip(ups, in_infos, reqs):
                    expands = lo(req) < 0 or hi(req) > extent(upi)
                    if not (expands or id(n) in spilled):
                        continue
                    if not self._inputs[id(u)]:
                        continue  # source: read-stage edge replication
                    if not transparent(u):
                        return False
                    spilled.add(id(u))
        return True

    # -- phase 1: UpdateOutputInformation -------------------------------------
    def update_information(self) -> Dict[int, ImageInfo]:
        """Propagate metadata downstream (nodes are stored in insertion order,
        which ``add`` guarantees is topological)."""
        if self._infos is None:
            infos: Dict[int, ImageInfo] = {}
            for node in self._nodes:
                in_infos = [infos[id(up)] for up in self._inputs[id(node)]]
                infos[id(node)] = node.output_info(*in_infos)
            self._infos = infos
        return self._infos

    def info(self, node: ProcessObject) -> ImageInfo:
        return self.update_information()[id(node)]

    # -- phases 2+3: eager pull ------------------------------------------------
    def pull(
        self,
        node: ProcessObject,
        out_region: ImageRegion,
        persistent_hook: Optional[Callable] = None,
        _cache: Optional[Dict] = None,
    ) -> jnp.ndarray:
        """Produce pixels of ``node`` for ``out_region`` (clamped + padded to
        the exact requested size).  ``persistent_hook(node, region, inputs)``
        is invoked for every PersistentFilter encountered (the streaming /
        parallel drivers use it to accumulate state)."""
        infos = self.update_information()
        cache = _cache if _cache is not None else {}
        key = (id(node), out_region)
        if key in cache:
            return cache[key]

        own_info = infos[id(node)]
        clamped = out_region.clamp(own_info.full_region)
        if clamped.is_empty():
            raise ValueError(f"{node.name}: request {out_region} outside image")

        ups = self._inputs[id(node)]
        if not ups:  # source
            data = node.generate(clamped)  # type: ignore[call-arg]
        else:
            in_infos = [infos[id(u)] for u in ups]
            reqs = node.requested_region(clamped, *in_infos)
            # the same window classification as the compiled plans, so the
            # eager pull is a bit-exact oracle for every executor (windows
            # shift float origins; needs_origin filters must treat any
            # request ⊇ the exact one identically up to rounding)
            reqs, _ = windowed_requests(node, clamped.size, reqs, in_infos)
            inputs = [
                self.pull(u, r, persistent_hook, cache) for u, r in zip(ups, reqs)
            ]
            if isinstance(node, PersistentFilter) and persistent_hook is not None:
                persistent_hook(node, clamped, inputs)
            if getattr(node, "needs_origin", False):
                data = node.generate(
                    clamped,
                    *inputs,
                    origin=clamped.index,
                    input_origins=tuple(r.index for r in reqs),
                )
            else:
                data = node.generate(clamped, *inputs)
        expect = (clamped.rows, clamped.cols)
        if tuple(data.shape[:2]) != expect:
            raise ValueError(
                f"{node.name}: generate() returned {data.shape[:2]}, expected {expect}"
            )
        data = boundary_pad(data, clamped, out_region)
        cache[key] = data
        return data

    # -- symbolic pull: describe (cheap) + lower (closure construction) --------
    def describe_pull(
        self, node: ProcessObject, out_region: ImageRegion,
        virtual: "bool | str" = False,
    ) -> PlanDescription:
        """The describe pass: reads + canonical signature + origin scalars
        for ``node`` over ``out_region``, with **no** closure construction.

        Runs the same recursion as :meth:`compile_pull` (so the signature is
        bit-identical) but skips building the O(graph) closure tree — on a
        plan-registry hit this is the only per-region graph work.

        ``virtual`` selects the padded-geometry walk: ``True`` / ``"grid"``
        never clamps in either axis, so a tile spilling past the image rows
        *or* columns yields the *interior* signature — the 2-D SPMD tile
        prober uses this to keep ragged grid splits on the registry path;
        ``"rows"`` is the restricted rows-only mode for pipelines where
        :meth:`virtual_cols_safe` is false."""
        return self._plan_walk(node, out_region, lower=False, virtual=virtual)

    def lower_pull(self, desc: PlanDescription) -> "PullPlan":
        """The lower pass: build the jittable closure for a described plan
        (re-walked in the description's real/virtual geometry mode).
        The plan registry calls this on misses only."""
        plan = self._plan_walk(
            desc.node, desc.out_region, lower=True, virtual=desc.virtual
        )
        assert plan.signature == desc.signature, (
            "describe/lower signature drift",
            desc.node.name,
        )
        return plan

    def compile_pull(self, node: ProcessObject, out_region: ImageRegion) -> "PullPlan":
        """Build a canonical :class:`PullPlan` for ``node`` over ``out_region``
        (describe + lower in one walk).

        ``canonical_fn(arrays, pstates, origins)`` maps source arrays (covering
        the plan's clamped source regions, in plan order), a persistent-state
        dict and the plan's dynamic origin scalars to
        ``(pixels, new_pstates)``.  Absolute coordinates of ``needs_origin``
        nodes are *not* baked in — they are read from ``origins`` so one
        compiled function serves every region with the same ``signature``."""
        return self._plan_walk(node, out_region, lower=True)

    def _plan_walk(
        self,
        node: ProcessObject,
        out_region: ImageRegion,
        lower: bool,
        virtual: "bool | str" = False,
    ):
        infos = self.update_information()
        virtual = _normalize_virtual(virtual)

        def clamp(region: ImageRegion, own_info: ImageInfo) -> ImageRegion:
            if not virtual:
                return region.clamp(own_info.full_region)
            if virtual == "grid":
                # fully virtual padded geometry: neither axis clamps — spill
                # in any direction is materialized at the read stage
                return region
            # "rows" mode: rows pass through unclamped (the read stage
            # materializes spilled rows by edge replication), columns still
            # clamp in-image so the column-pad statics match the real
            # interior signature
            c0 = max(region.col0, 0)
            c1 = min(region.col1, own_info.cols)
            if c1 < c0:
                c1 = c0
            return ImageRegion((region.row0, c0), (region.rows, c1 - c0))
        reads: List[Tuple[Source, ImageRegion, ImageRegion]] = []
        read_windows: List[Optional[Tuple[int, int]]] = []
        read_index: Dict[Tuple, int] = {}
        origin_values: List[int] = []
        sig: List[Tuple] = []  # canonical step records, built by recursion
        persistent: List[PersistentFilter] = []
        built: Dict[Tuple, Tuple[int, Callable]] = {}
        pallas_serials: List[int] = []  # nodes lowered to fused Pallas bodies
        fused_serials: List[int] = []  # pointwise nodes folded into a body

        # Pallas fusion census: a pointwise node may fold into its consumer's
        # kernel only when it has exactly ONE consumer in the graph —
        # otherwise its pixels are needed materialized elsewhere
        consumers: Dict[int, int] = {}
        for _n in self._nodes:
            for _u in self._inputs[id(_n)]:
                consumers[id(_u)] = consumers.get(id(_u), 0) + 1

        def fuse_chain(u, req):
            """Walk the run of fusable pointwise nodes up one input edge.

            Returns ``(chain, deep, deep_req)``: ``chain`` is the
            consumer→producer list of ``(node, pointwise_fn)`` folded into
            the kernel, ``deep`` the first node that stays materialized, and
            ``deep_req`` the region requested of it.  A node fuses only when
            it is a single-input, single-consumer pointwise filter
            (``pointwise_fn() is not None``) on its input's grid, with an
            identity requested region and no origin / persistent / plan-key
            semantics — anything else refuses and the chain stops there.
            The decision uses graph structure and static node state only, so
            the describe and the lower walk always agree.  Because every
            link shares one grid, the deep node clamps and edge-pads ``req``
            exactly where each chain node would have, and pointwise fns
            commute with edge padding — fused output is bit-equal to the
            unfused chain feeding the same kernel.
            """
            chain: List[Tuple[ProcessObject, Callable]] = []
            cur = u
            while True:
                fn = cur.pointwise_fn()
                if (
                    fn is None
                    or cur.n_inputs != 1
                    or consumers.get(id(cur), 0) != 1
                    or isinstance(cur, (PersistentFilter, Mapper))
                    or getattr(cur, "needs_origin", False)
                    or cur.plan_key(req) is not None
                ):
                    return chain, cur, req
                up = self._inputs[id(cur)][0]
                own, upi = infos[id(cur)], infos[id(up)]
                if (own.rows, own.cols) != (upi.rows, upi.cols):
                    return chain, cur, req
                if tuple(cur.requested_region(req, upi)) != (req,):
                    return chain, cur, req
                chain.append((cur, fn))
                cur = up

        def dyn(value: int) -> int:
            """Register a dynamic (traced) origin scalar; returns its slot."""
            origin_values.append(int(value))
            return len(origin_values) - 1

        def memoize(key, fn):
            # one evaluation per distinct (node, region) request per call —
            # mirrors the eager pull's request cache (and keeps persistent
            # accumulation from double-counting diamond fan-in)
            def run(arrays, origins, ctx, _key=key, _fn=fn):
                if _key in ctx["memo"]:
                    return ctx["memo"][_key]
                out = _fn(arrays, origins, ctx)
                ctx["memo"][_key] = out
                return out

            return run

        def build(
            n: ProcessObject, region: ImageRegion, in_window: bool = False
        ) -> Optional[Callable]:
            key = (id(n), region, in_window)
            if key in built:
                ordinal, fn = built[key]
                sig.append(("ref", ordinal))
                return fn
            ordinal = len(built)
            own_info = infos[id(n)]
            clamped = clamp(region, own_info)
            # boundary-pad widths are baked into the trace → part of the key
            pads = (
                clamped.row0 - region.row0,
                region.row1 - clamped.row1,
                clamped.col0 - region.col0,
                region.col1 - clamped.col1,
            )
            ups = self._inputs[id(n)]
            if not ups:
                if in_window:
                    # a windowed read's clamped rect is read-stage-only (the
                    # delivered array is always padded to the full window),
                    # so it is WALK-MODE-INDEPENDENT: rows pass through (the
                    # read stage's snap handles fully-virtual rows), columns
                    # clamp in-image (window_request anchors windows
                    # in-image) — real and virtual describes of one window
                    # record identical reads
                    c0 = max(region.col0, 0)
                    c1 = max(c0, min(region.col1, own_info.cols))
                    clamped = ImageRegion(
                        (region.row0, c0), (region.rows, c1 - c0)
                    )
                # non-windowed reads dedup on the clamped rect alone (the
                # per-consumer spill pad is baked in the trace); windowed
                # reads pad to their window at the read stage, so the window
                # region is part of their identity
                k = (
                    (id(n), clamped, region, True)
                    if in_window
                    else (id(n), clamped)
                )
                if k not in read_index:
                    read_index[k] = len(reads)
                    reads.append((n, clamped, region))  # type: ignore[arg-type]
                    read_windows.append(region.size if in_window else None)
                idx = read_index[k]
                # tiled/range-readable sources stamp their storage geometry
                # (tile size, overview level) into the read record — see
                # Source.read_record
                rrec = n.read_record()
                if in_window:
                    # windowed read: static window shape, no pads in the
                    # trace — border spill is materialized at the READ stage
                    # (host boundary_pad / SPMD halo replication), so border
                    # regions share the interior signature
                    sig.append(("wread", n._serial, idx, region.size,
                                np.dtype(own_info.dtype).str, own_info.bands,
                                rrec))
                else:
                    sig.append(("read", n._serial, idx, clamped.size, pads,
                                np.dtype(own_info.dtype).str, own_info.bands,
                                rrec))
                fn = None
                if lower:
                    if in_window:

                        def run_source(arrays, origins, ctx, _idx=idx):
                            return arrays[_idx]

                    else:

                        def run_source(arrays, origins, ctx, _idx=idx,
                                       _clamped=clamped, _region=region):
                            return boundary_pad(arrays[_idx], _clamped, _region)

                    fn = memoize(key, run_source)
                built[key] = (ordinal, fn)
                return fn

            in_infos = [infos[id(u)] for u in ups]
            reqs = n.requested_region(clamped, *in_infos)
            # window classification: a needs_origin node's drifting requests
            # become conservative static-shape windows (traced origins), so
            # every same-size region lowers to ONE shared trace
            reqs, wbounds = windowed_requests(n, clamped.size, reqs, in_infos)
            origin_aware = bool(getattr(n, "needs_origin", False))
            persist = isinstance(n, PersistentFilter)
            # Pallas fast path: decided identically in describe AND lower
            # (lower_pull re-asserts signature equality).  Origin-aware and
            # persistent nodes keep the generic lowering — their traced
            # scalars / state threading stay outside kernel bodies.
            pallas_on = not origin_aware and not persist and n.pallas_plan()
            if pallas_on:
                fusions = [fuse_chain(u, r) for u, r in zip(ups, reqs)]
                child_fns = [
                    build(deep, dreq, in_window) for _, deep, dreq in fusions
                ]
            else:
                child_fns = [
                    build(u, r, in_window or wb is not None)
                    for u, r, wb in zip(ups, reqs, wbounds)
                ]
            if persist and n not in persistent:
                persistent.append(n)
            oi = (dyn(clamped.row0), dyn(clamped.col0)) if origin_aware else None
            ii = (
                tuple((dyn(r.row0), dyn(r.col0)) for r in reqs)
                if origin_aware
                else None
            )
            # mask-aware persistent filters always thread their absolute
            # (row, col) origin as traced scalars: the in-trace 2-D validity
            # mask is all-true on real geometry and masks virtual pad
            # rows/cols under padded SPMD tiles — one registry body serves
            # both (slot registration must not depend on the walk mode, or
            # real/virtual plans with equal signatures would disagree on the
            # origin vector length)
            mi = (
                (dyn(clamped.row0), dyn(clamped.col0))
                if persist and n.supports_mask
                else None
            )
            winb = wbounds if any(b is not None for b in wbounds) else None
            if pallas_on:
                # fused chain nodes contribute no records of their own; the
                # kernel's record carries their serials, so fused and unfused
                # plans of one graph can never share a registry entry
                fused = tuple(
                    tuple(c._serial for c, _ in chain) for chain, _, _ in fusions
                )
                sig.append(
                    ("pallas", n._serial, clamped.size, pads,
                     n.plan_key(clamped), fused)
                )
                pallas_serials.append(n._serial)
                for chain, _, _ in fusions:
                    fused_serials.extend(c._serial for c, _ in chain)
            else:
                sig.append(
                    ("node", n._serial, clamped.size, pads, origin_aware,
                     persist, n.plan_key(clamped), winb)
                )
            fn = None
            if lower and pallas_on:
                pre_fns: List[Optional[Callable]] = []
                for chain, _, _ in fusions:
                    if not chain:
                        pre_fns.append(None)
                        continue
                    chain_fns = tuple(f for _, f in chain)

                    def composed(t, _fns=chain_fns):
                        # chain[0] sits nearest the kernel: apply deepest-first
                        for g in reversed(_fns):
                            t = g(t)
                        return t

                    pre_fns.append(composed)
                body = n.pallas_body(tuple(pre_fns))

                def run_pallas(arrays, origins, ctx, _body=body,
                               _clamped=clamped, _region=region,
                               _fns=child_fns):
                    ins = [f(arrays, origins, ctx) for f in _fns]
                    return boundary_pad(_body(*ins), _clamped, _region)

                fn = memoize(key, run_pallas)
            elif lower:

                def run_node(arrays, origins, ctx, _n=n, _clamped=clamped,
                             _region=region, _fns=child_fns, _oi=oi, _ii=ii,
                             _persist=persist, _mi=mi,
                             _rows_total=own_info.rows,
                             _cols_total=own_info.cols):
                    ins = [f(arrays, origins, ctx) for f in _fns]
                    if _persist:
                        if _mi is not None:
                            rows_abs = origins[_mi[0]] + jnp.arange(_clamped.rows)
                            cols_abs = origins[_mi[1]] + jnp.arange(_clamped.cols)
                            rv = (rows_abs >= 0) & (rows_abs < _rows_total)
                            cv = (cols_abs >= 0) & (cols_abs < _cols_total)
                            mask = rv[:, None, None] & cv[None, :, None]
                            ctx["pstates"][_n.name] = _n.accumulate(
                                ctx["pstates"][_n.name], _clamped, *ins,
                                mask=mask,
                            )
                        else:
                            ctx["pstates"][_n.name] = _n.accumulate(
                                ctx["pstates"][_n.name], _clamped, *ins
                            )
                    if _oi is not None:
                        out = _n.generate(
                            _clamped,
                            *ins,
                            origin=(origins[_oi[0]], origins[_oi[1]]),
                            input_origins=tuple(
                                (origins[a], origins[b]) for a, b in _ii
                            ),
                        )
                    else:
                        out = _n.generate(_clamped, *ins)
                    return boundary_pad(out, _clamped, _region)

                fn = memoize(key, run_node)
            built[key] = (ordinal, fn)
            return fn

        root = build(node, out_region)
        persistent_nodes = list(persistent)
        static_origins = tuple(origin_values)

        if not lower:
            return PlanDescription(
                node=node,
                out_region=out_region,
                reads=reads,
                signature=tuple(sig),
                origin_values=static_origins,
                persistent_nodes=persistent_nodes,
                windows=tuple(read_windows),
                virtual=virtual,
                pad_rows=(
                    max(0, out_region.row1 - infos[id(node)].rows)
                    if virtual
                    else 0
                ),
                pad_cols=(
                    max(0, out_region.col1 - infos[id(node)].cols)
                    if virtual == "grid"
                    else 0
                ),
                pallas_nodes=tuple(pallas_serials),
                fused_nodes=tuple(fused_serials),
            )

        def canonical_fn(arrays, pstates, origins):
            ctx = {"pstates": dict(pstates), "memo": {}}
            out = root(arrays, origins, ctx)
            return out, ctx["pstates"]

        return PullPlan(
            reads=reads,
            out_region=out_region,
            canonical_fn=canonical_fn,
            signature=tuple(sig),
            origin_values=static_origins,
            persistent_nodes=persistent_nodes,
            windows=tuple(read_windows),
            pallas_nodes=tuple(pallas_serials),
            fused_nodes=tuple(fused_serials),
        )


@dataclasses.dataclass
class PullPlan:
    """``reads``: list of (source, clamped_region, requested_region).

    ``canonical_fn(arrays, pstates, origins)``, with arrays[i] covering
    reads[i]'s clamped region, returns ``(pixels, new_pstates)``:
    ``origins`` carries the absolute coordinates consumed by ``needs_origin``
    nodes as traced scalars and ``pstates`` threads persistent-filter state,
    so one jit of ``canonical_fn`` serves every region whose ``signature``
    matches this plan's."""

    reads: List[Tuple[Source, ImageRegion, ImageRegion]]
    out_region: ImageRegion
    canonical_fn: Optional[Callable] = None
    signature: Tuple = ()
    origin_values: Tuple[int, ...] = ()
    persistent_nodes: List[PersistentFilter] = dataclasses.field(
        default_factory=list
    )
    #: per read, the static (rows, cols) window-spec shape for windowed reads
    #: (``needs_origin`` bounding windows), or None for exact covariant reads
    windows: Tuple[Optional[Tuple[int, int]], ...] = ()
    #: serials of nodes lowered to fused Pallas bodies / of pointwise nodes
    #: folded into one (diagnostic mirrors of the signature's pallas records)
    pallas_nodes: Tuple[int, ...] = ()
    fused_nodes: Tuple[int, ...] = ()

    def read_sources(self) -> List[jnp.ndarray]:
        return read_plan_sources(self.reads, self.windows)

    def origins(self) -> Tuple[np.int32, ...]:
        """Per-region dynamic origin scalars, in canonical slot order.  Passed
        as arrays so jit traces (not bakes) them."""
        return tuple(np.int32(v) for v in self.origin_values)

    def initial_pstates(self) -> Dict[str, Dict[str, jnp.ndarray]]:
        return {p.name: p.reset() for p in self.persistent_nodes}
