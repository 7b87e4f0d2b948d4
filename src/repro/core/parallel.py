"""Cluster-parallel pipeline execution (paper §II.C) — TPU/JAX-native.

The paper runs one *pipeline replica per MPI process*, each producing a
different region of the output; persistent filters aggregate state with MPI
collectives.  Here the whole pipeline is traced once into a *local tile
function* and partitioned with ``shard_map`` over a 2-D device mesh:

  * the output domain is decomposed into an ``nr × nc`` grid of contiguous
    tiles (the paper's striped scheme is the ``nc = 1`` column of this grid,
    not a separate code path);
  * requested-region propagation is evaluated symbolically for *every*
    tile to derive, per source, the tile pitches (resolution scale per axis)
    and the row/column halos each device must fetch from its neighbors —
    the MPI point-to-point of the paper becomes ``lax.ppermute`` neighbor
    exchange along each mesh axis;
  * boundary devices edge-replicate their own rows/columns (ITK boundary
    condition), so the parallel result matches the streamed oracle — the
    paper's region-independence invariant (§II.C.1);
  * persistent filters accumulate per-device state which is combined with
    ``lax.psum`` / ``pmax`` / ``pmin`` / ``all_gather`` over both mesh axes
    (the paper's many-to-one / many-to-many MPI patterns), then
    ``synthesize`` runs once.

Two kinds of reads feed filters:

  * *covariant reads* — the request shifts by a constant integer pitch per
    tile row/column with constant size (box filters, integer-ratio
    resampling).  The planner slices the exact requested window from the
    haloed local shard; this is checked against the probes of all tiles.
  * *windowed reads* — requests of ``needs_origin`` filters (warps) whose
    exact windows drift fractionally per tile.  The describe pass lowers
    them to the plan layer's *window specs* (``ProcessObject.window_bound``):
    conservative static-shape bounding windows whose absolute origins are
    traced scalars.  Constant shape means one canonical plan for every
    interior tile; the per-tile window origin becomes a constant table
    gathered at the flat mesh index, and the window itself is a
    ``lax.dynamic_slice`` of the halo-exchanged local shard.

Anything else (data-dependent regions, non-affine request growth, drifting
``needs_origin`` reads without a ``window_bound``, per-tile plan keys, or a
``nc > 1`` grid over a pipeline whose column borders are not
virtualization-safe) raises ``NotTileParallelizable`` with diagnostics and
should run through the streaming driver (``NotStripParallelizable`` remains
as an alias).

**Unified ExecutionPlan path** — the *only* SPMD path.  ``build_tile_plan``
runs the cheap describe pass (``Pipeline.describe_pull``) for every tile of
the **virtual padded grid** (rows padded up to ``nr × Hr``, columns up to
``nc × Wc``; the ``"grid"`` describe walk never clamps in either axis), so
every tile — the ragged right/bottom edges of an uneven split and the border
tiles of small grids included — yields the *interior* plan signature.  All
tiles must share that one signature; the tile body is then fetched from the
shared :class:`~repro.core.execplan.PlanCache` — the very same registry (and
the very same lowered closure) the streaming engine uses.  A pipeline
streamed first and then run SPMD on any tile geometry is therefore a
registry *hit*: no new describe→lower pass, no new closure tree.  Per-tile
``needs_origin`` coordinates (covariant, window *and* persistent-mask
origins alike) are threaded as per-tile constant tables gathered at the flat
``(row, col)`` mesh index; plan reads are static slices of the
halo-exchanged local shard when their offsets are tile-invariant and
``lax.dynamic_slice`` windows otherwise.  Spill past the real image — halos
and virtual pad rows/columns — is materialized at the read stage
(edge-padded global + halo edge replication), never in the trace.
Masked-persistent accumulation is the only special case left, and it runs
through the same registry body: mask-aware filters accumulate under an
in-trace 2-D validity mask derived from their traced (row, col) origin, so
pad pixels never contaminate reduced state; the executor crops the pad
before the write stage, keeping outputs bit-identical to the streaming
oracle.  The jitted SPMD program itself is registered in the same cache
under its geometry key, so repeated executors on one pipeline reuse one
program.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.execplan import PlanCache
from repro.core.pipeline import Pipeline
from repro.core.splitting import padded_tile_grid, virtual_tile_regions
from repro.core.process_object import (
    ImageInfo,
    Mapper,
    ProcessObject,
    Reduction,
    Source,
    windowed_requests,
)
from repro.core.region import ImageRegion


class NotTileParallelizable(ValueError):
    """Raised when the graph violates the shard_map tile-grid requirements
    (with diagnostics naming the offending node/axis/geometry)."""


#: back-compat alias — the 1-D strip path is the ``nc = 1`` column of the
#: tile grid, and its failure mode is the same exception
NotStripParallelizable = NotTileParallelizable


# ---------------------------------------------------------------------------
# halo exchange
# ---------------------------------------------------------------------------
def halo_exchange_rows(
    x: jnp.ndarray, halo_top: int, halo_bot: int, axis_name: str, n: int
) -> jnp.ndarray:
    """Fetch ``halo_top`` rows from the device above and ``halo_bot`` rows
    from the device below via ``ppermute``; boundary devices edge-replicate
    their own first/last row (matches the streamed oracle's boundary_pad)."""
    if n == 1 or (halo_top == 0 and halo_bot == 0):
        pad = [(halo_top, halo_bot)] + [(0, 0)] * (x.ndim - 1)
        return jnp.pad(x, pad, mode="edge") if (halo_top or halo_bot) else x
    if halo_top > x.shape[0] or halo_bot > x.shape[0]:
        raise NotTileParallelizable(
            f"halo ({halo_top}/{halo_bot}) exceeds strip rows ({x.shape[0]}); "
            "use fewer workers or the streaming driver"
        )
    idx = lax.axis_index(axis_name)
    parts = []
    if halo_top:
        from_above = lax.ppermute(
            x[-halo_top:], axis_name, [(i, i + 1) for i in range(n - 1)]
        )
        edge = jnp.repeat(x[:1], halo_top, axis=0)
        parts.append(jnp.where(idx == 0, edge, from_above))
    parts.append(x)
    if halo_bot:
        from_below = lax.ppermute(
            x[:halo_bot], axis_name, [(i + 1, i) for i in range(n - 1)]
        )
        edge = jnp.repeat(x[-1:], halo_bot, axis=0)
        parts.append(jnp.where(idx == n - 1, edge, from_below))
    return jnp.concatenate(parts, axis=0)


def halo_exchange_cols(
    x: jnp.ndarray, halo_left: int, halo_right: int, axis_name: str, n: int
) -> jnp.ndarray:
    """Column mirror of :func:`halo_exchange_rows`: fetch ``halo_left``
    columns from the device to the left and ``halo_right`` from the right
    via ``ppermute`` along the column mesh axis; boundary devices
    edge-replicate their own first/last column.  At ``n = 1`` this is a pure
    edge pad — exactly how the 1-D strip path materializes column spill."""
    if n == 1 or (halo_left == 0 and halo_right == 0):
        pad = [(0, 0), (halo_left, halo_right)] + [(0, 0)] * (x.ndim - 2)
        return jnp.pad(x, pad, mode="edge") if (halo_left or halo_right) else x
    if halo_left > x.shape[1] or halo_right > x.shape[1]:
        raise NotTileParallelizable(
            f"halo ({halo_left}/{halo_right}) exceeds tile cols ({x.shape[1]}); "
            "use fewer column workers or the streaming driver"
        )
    idx = lax.axis_index(axis_name)
    parts = []
    if halo_left:
        from_left = lax.ppermute(
            x[:, -halo_left:], axis_name, [(i, i + 1) for i in range(n - 1)]
        )
        edge = jnp.repeat(x[:, :1], halo_left, axis=1)
        parts.append(jnp.where(idx == 0, edge, from_left))
    parts.append(x)
    if halo_right:
        from_right = lax.ppermute(
            x[:, :halo_right], axis_name, [(i + 1, i) for i in range(n - 1)]
        )
        edge = jnp.repeat(x[:, -1:], halo_right, axis=1)
        parts.append(jnp.where(idx == n - 1, edge, from_right))
    return jnp.concatenate(parts, axis=1)


# ---------------------------------------------------------------------------
# symbolic tile-plan extraction
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class SourceTile:
    source: Source
    pitch_r: int  # input rows per output tile row (resolution scale × Hr)
    pitch_c: int  # input cols per output tile col (resolution scale × Wc)
    halo_top: int
    halo_bot: int
    halo_left: int
    halo_right: int

    @property
    def pitch(self) -> int:  # back-compat: the 1-D strip path's row pitch
        return self.pitch_r


#: back-compat alias for the 1-D strip path's per-source record
SourceStrip = SourceTile


@dataclasses.dataclass
class TilePlan:
    """Everything needed to run the pipeline as one SPMD program over an
    ``nr × nc`` tile grid (1-D strip plans are the ``nc = 1`` column)."""

    grid: Tuple[int, int]  # (nr, nc)
    tile_rows: int  # output rows per device tile (Hr)
    tile_cols: int  # output cols per device tile (Wc)
    out_info: ImageInfo
    source_tiles: List[SourceTile]
    #: fn(local_arrays, flat_idx) -> (out_tile, {pname: state}); flat_idx is
    #: the row-major (row, col) mesh index ``ir * nc + ic``
    fn: Callable
    #: always True since the virtual-padded path retired the legacy
    #: hand-rolled closure: every tile body IS the shared canonical plan
    #: from the ExecutionPlan registry (kept as a field for introspection /
    #: back-compat with callers that asserted on it)
    unified: bool = True
    #: canonical signature of the shared per-tile plan
    plan_signature: Optional[Tuple] = None
    #: trailing virtual pad rows/cols past the real image (cropped by the
    #: executor before the write stage; masked out of persistent state)
    pad_rows: int = 0
    pad_cols: int = 0
    #: registry key prefix for the jitted SPMD program (device ids appended
    #: by the executor)
    program_key: Tuple = ()

    @property
    def n_workers(self) -> int:
        return self.grid[0] * self.grid[1]

    @property
    def strip_rows(self) -> int:  # back-compat: 1-D strip height
        return self.tile_rows

    @property
    def source_strips(self) -> List[SourceTile]:  # back-compat
        return self.source_tiles


#: back-compat alias — a strip plan IS a tile plan with ``grid = (n, 1)``
StripPlan = TilePlan


def _probe_edges(pipeline: Pipeline, mapper: Mapper, tile: ImageRegion):
    """Unclamped requested-region propagation for one worker tile, with the
    same window classification as the describe pass (``needs_origin``
    requests become static-shape bounding windows).  Returns a DFS-ordered
    list of (parent_or_None, node, region, in_window) — every
    producer→consumer edge occurrence plus the root."""
    infos = pipeline.update_information()
    edges = []

    def walk(parent, node: ProcessObject, region: ImageRegion, in_window: bool):
        edges.append((parent, node, region, in_window))
        ups = pipeline.inputs_of(node)
        if not ups:
            return
        in_infos = [infos[id(u)] for u in ups]
        reqs = node.requested_region(region, *in_infos)
        reqs, wbounds = windowed_requests(node, region.size, reqs, in_infos)
        for u, r, wb in zip(ups, reqs, wbounds):
            walk(node, u, r, in_window or wb is not None)

    walk(None, mapper, tile, False)
    return edges


def _unified_tile_fn(
    pipeline: Pipeline,
    mapper: Mapper,
    grid: Tuple[int, int],
    out_info: ImageInfo,
    tile_by_source: Dict[int, SourceTile],
    plan_cache: PlanCache,
    describe_virtual: "bool | str",
):
    """Build the per-tile body from the shared ExecutionPlan registry.

    Runs the *virtual* describe pass for every tile of the grid (host-side,
    cheap, against the padded geometry — so ragged edge tiles and small-grid
    border tiles describe like interior ones), requires every tile to share
    one canonical signature, and fetches/lowers the canonical closure
    through ``plan_cache`` so the SPMD program traces the *same* plan the
    streaming engine compiles for the equivalent regions.  Per-tile
    ``needs_origin`` coordinates (covariant origins, windowed-read origins
    and persistent-mask origins alike) become constant per-tile tables
    gathered at the flat mesh index; plan reads whose offsets are
    tile-invariant stay static slices of the halo-exchanged local shard,
    drifting window reads lower to ``lax.dynamic_slice`` at table offsets.

    Returns ``(tile_fn, description)``; raises
    :class:`NotTileParallelizable` when the geometry cannot share one
    interior trace (per-tile plan keys, mismatched walk shapes, reads
    outside the haloed shard, unmaskable persistent state on a padded
    split).
    """
    nr, nc = grid
    n = nr * nc
    persistent = pipeline.persistent_nodes()
    descs = [
        pipeline.describe_pull(mapper, tile, virtual=describe_virtual)
        for tile in virtual_tile_regions(out_info.rows, out_info.cols, nr, nc)
    ]
    kp = (nr // 2) * nc + nc // 2  # a canonical interior tile
    d0 = descs[kp]
    if any(d.pad_rows or d.pad_cols for d in descs):
        unmaskable = [p.name for p in d0.persistent_nodes if not p.supports_mask]
        if unmaskable:
            raise NotTileParallelizable(
                f"image ({out_info.rows}×{out_info.cols}) doesn't divide over "
                f"the {nr}×{nc} grid and persistent filter(s) {unmaskable} "
                "are not mask-aware (set supports_mask and handle `mask`); "
                "use the streaming driver or a grid that divides the image"
            )
    mismatched = [k for k in range(n) if descs[k].signature != d0.signature]
    if mismatched:
        raise NotTileParallelizable(
            f"tiles {[(k // nc, k % nc) for k in mismatched]} of the "
            f"{nr}×{nc} grid do not share the canonical interior plan "
            "signature (per-tile plan keys — e.g. a resampling phase "
            "misaligned with the tile dimensions — or non-uniform walk "
            "geometry); use the streaming driver or change the grid"
        )
    nslots = len(d0.origin_values)
    if any(len(descs[k].origin_values) != nslots for k in range(n)) or any(
        len(descs[k].reads) != len(d0.reads) for k in range(n)
    ):
        raise NotTileParallelizable(
            "per-tile describe walks disagree in shape; use the streaming "
            "driver"
        )

    # per-slot origin tables over the flat mesh index: a constant gather
    # handles every per-tile drift the describe pass produced (affine or not)
    tables = [
        tuple(int(descs[k].origin_values[i]) for k in range(n))
        for i in range(nslots)
    ]

    # every plan read is a window of the halo-exchanged shard: a static slice
    # when its offset is tile-invariant, a dynamic_slice at per-tile table
    # offsets otherwise (drifting windowed reads); windowed reads deliver the
    # full static window shape (row/col spill comes from halo edge
    # replication — the trace carries no pads for them)
    read_specs = []
    for i, (src, clamped, req) in enumerate(d0.reads):
        ss = tile_by_source.get(id(src))
        if ss is None or any(
            descs[k].reads[i][0] is not src for k in range(n)
        ) or any(
            descs[k].reads[i][2].size != req.size for k in range(n)
        ):
            raise NotTileParallelizable(
                f"{src.name}: per-tile reads disagree with the probe "
                "geometry; use the streaming driver"
            )
        local_rows = ss.pitch_r + ss.halo_top + ss.halo_bot
        local_cols = ss.pitch_c + ss.halo_left + ss.halo_right
        windowed = i < len(d0.windows) and d0.windows[i] is not None
        # windowed reads deliver the full static window (reads[i][2]); exact
        # reads deliver the clamped rect (reads[i][1] — identical to the
        # request under "grid" describes, column-clamped under "rows")
        rows, ncols = (req.size if windowed else clamped.size)
        pick = 2 if windowed else 1
        roffs = [
            descs[k].reads[i][pick].row0
            - ((k // nc) * ss.pitch_r - ss.halo_top)
            for k in range(n)
        ]
        coffs = [
            descs[k].reads[i][pick].col0
            - ((k % nc) * ss.pitch_c - ss.halo_left)
            for k in range(n)
        ]
        if any(o < 0 or o + rows > local_rows for o in roffs) or any(
            c < 0 or c + ncols > local_cols for c in coffs
        ):
            raise NotTileParallelizable(
                f"{src.name}: a tile's read spills outside the haloed local "
                f"shard ({local_rows}×{local_cols}); use fewer workers or "
                "the streaming driver"
            )
        # static only when EVERY tile (border tiles run this trace too, via
        # halo replication) agrees on the shard offset
        if all(roffs[k] == roffs[kp] and coffs[k] == coffs[kp]
               for k in range(n)):
            read_specs.append((id(src), False, roffs[kp], coffs[kp], rows, ncols))
        else:
            read_specs.append(
                (id(src), True, tuple(roffs), tuple(coffs), rows, ncols)
            )

    entry = plan_cache.compiled_for(d0, lambda: pipeline.lower_pull(d0))
    canonical = entry.canonical_fn

    def tile_fn(local_arrays: Dict[int, jnp.ndarray], flat_idx):
        arrays = []
        for sid, dyn_read, roff, coff, rows, ncols in read_specs:
            local = local_arrays[sid]
            if dyn_read:
                r = jnp.asarray(roff, jnp.int32)[flat_idx]
                c = jnp.asarray(coff, jnp.int32)[flat_idx]
                arr = lax.dynamic_slice(
                    local,
                    (r, c) + (0,) * (local.ndim - 2),
                    (rows, ncols) + tuple(local.shape[2:]),
                )
            else:
                arr = local[roff:roff + rows, coff:coff + ncols]
            arrays.append(arr)
        origins = tuple(
            jnp.int32(t[0]) if len(set(t)) == 1
            else jnp.asarray(t, jnp.int32)[flat_idx]
            for t in tables
        )
        pstates = {p.name: p.reset() for p in persistent}
        return canonical(arrays, pstates, origins)

    return tile_fn, d0


def build_tile_plan(
    pipeline: Pipeline,
    mapper: Mapper,
    grid: Tuple[int, int],
    axis_name: str = "workers",
    plan_cache: Optional[PlanCache] = None,
) -> TilePlan:
    """Probe, validate and assemble the unified SPMD plan for an ``nr × nc``
    tile grid.  ``build_strip_plan`` is the ``(n, 1)`` wrapper."""
    nr, nc = grid
    if nr <= 0 or nc <= 0:
        raise ValueError(f"grid dims must be positive, got {grid}")
    n = nr * nc
    infos = pipeline.update_information()
    out_info = infos[id(mapper)]
    Hr, Wc, pad_rows, pad_cols = padded_tile_grid(
        out_info.rows, out_info.cols, nr, nc
    )
    tiles = virtual_tile_regions(out_info.rows, out_info.cols, nr, nc)

    # column sharding demands fully-virtual ("grid") describes; at nc == 1
    # a pipeline that only supports "rows" (or nothing) keeps the legacy
    # rows-only virtualization so strip behavior is unchanged
    mode = pipeline.virtual_describe_mode()
    if nc > 1 and mode != "grid":
        unmaskable = [
            p.name for p in pipeline.persistent_nodes() if not p.supports_mask
        ]
        if unmaskable:
            why = f"persistent filter(s) {unmaskable} are not mask-aware"
        elif not pipeline.virtual_rows_safe():
            why = (
                "row-border spill reaches an intermediate row-stencil filter "
                "(virtual_rows_safe() is False)"
            )
        else:
            why = (
                "column-border spill reaches an intermediate column-stencil "
                "filter (virtual_cols_safe() is False)"
            )
        raise NotTileParallelizable(
            f"a {nr}×{nc} tile grid needs fully-virtual ('grid') describes, "
            f"but {why}; use an (n, 1) strip grid or the streaming driver"
        )
    describe_virtual = mode if mode else "rows"

    # --- probe EVERY worker's tile (host-side, cheap) ------------------------
    probes = [_probe_edges(pipeline, mapper, tile) for tile in tiles]
    if any(len(p) != len(probes[0]) for p in probes):
        raise NotTileParallelizable("graph shape varies per tile")

    #: per source: list of (pitch_r_or_None, pitch_c_or_None,
    #: [(row0, row1)], [(col0, col1)]) over all tiles, flat row-major order
    src_reads: Dict[int, List[Tuple]] = {}

    for i, (parent0, node0, r0, win0) in enumerate(probes[0]):
        occs = [p[i][2] for p in probes]
        if any(p[i][1] is not node0 for p in probes):
            raise NotTileParallelizable("graph traversal varies per tile")
        is_src = not pipeline.inputs_of(node0)
        row_ranges = [(r.row0, r.row1) for r in occs]
        col_ranges = [(r.col0, r.col1) for r in occs]
        if any(r.size != occs[0].size for r in occs):
            raise NotTileParallelizable(
                f"{node0.name}: requested-region size varies per tile"
            )
        if win0:
            # window spec subtree: static shape by construction, origins may
            # drift freely (the unified path tables them per tile)
            if is_src:
                src_reads.setdefault(id(node0), []).append(
                    (None, None, row_ranges, col_ranges)
                )
            continue
        # covariant edge: constant size, a constant integer pitch per grid
        # axis, and no cross-axis drift (row origin independent of the tile
        # column and vice versa)
        pr = occs[nc].row0 - occs[0].row0 if nr > 1 else 0
        pc = occs[1].col0 - occs[0].col0 if nc > 1 else 0
        bad = [
            k for k in range(n)
            if occs[k].row0 != occs[0].row0 + (k // nc) * pr
            or occs[k].col0 != occs[0].col0 + (k % nc) * pc
        ]
        if bad:
            hint = (
                "; declare a window_bound on the requesting needs_origin "
                "filter to lower the drift to a windowed read"
                if parent0 is not None
                and getattr(parent0, "needs_origin", False)
                else ""
            )
            raise NotTileParallelizable(
                f"{node0.name}: requested regions are not translation-"
                f"covariant over the {nr}×{nc} grid (tiles "
                f"{[(k // nc, k % nc) for k in bad[:4]]} break the affine "
                f"row-pitch {pr} / col-pitch {pc} pattern){hint}"
            )
        if is_src:
            if nr > 1 and pr <= 0:
                raise NotTileParallelizable(
                    f"{node0.name}: non-positive row pitch {pr}"
                )
            if nc > 1 and pc <= 0:
                raise NotTileParallelizable(
                    f"{node0.name}: non-positive col pitch {pc}"
                )
            src_reads.setdefault(id(node0), []).append(
                (pr, pc, row_ranges, col_ranges)
            )

    # --- per-source sharding pitches + combined halos over all reads/tiles ---
    source_tiles: List[SourceTile] = []
    tile_by_source: Dict[int, SourceTile] = {}
    for src in pipeline.sources():
        recs = src_reads.get(id(src))
        if not recs:
            continue
        src_info = infos[id(src)]
        cov_pr = {pr for pr, _, _, _ in recs if pr is not None}
        cov_pc = {pc for _, pc, _, _ in recs if pc is not None}
        if len(cov_pr) > 1 or len(cov_pc) > 1:
            raise NotTileParallelizable(
                f"{src.name}: conflicting pitches across reads "
                f"(rows {sorted(cov_pr)}, cols {sorted(cov_pc)})"
            )
        if cov_pr:
            # a 1-device axis holds the whole extent (covariant pitch is 0
            # there — no second tile to difference against)
            pitch_r = src_info.rows if nr == 1 else cov_pr.pop()
            pitch_c = src_info.cols if nc == 1 else cov_pc.pop()
        else:
            pitch_r = math.ceil(src_info.rows / nr)
            pitch_c = math.ceil(src_info.cols / nc)
        halo_top = halo_bot = halo_left = halo_right = 0
        for _, _, row_ranges, col_ranges in recs:
            for k in range(n):
                ti, tj = k // nc, k % nc
                a0, a1 = row_ranges[k]
                c0, c1 = col_ranges[k]
                halo_top = max(halo_top, ti * pitch_r - a0)
                halo_bot = max(halo_bot, a1 - (ti + 1) * pitch_r)
                halo_left = max(halo_left, tj * pitch_c - c0)
                halo_right = max(halo_right, c1 - (tj + 1) * pitch_c)
        ss = SourceTile(
            src, pitch_r, pitch_c,
            max(0, halo_top), max(0, halo_bot),
            max(0, halo_left), max(0, halo_right),
        )
        source_tiles.append(ss)
        tile_by_source[id(src)] = ss

    geom = tuple(
        (ss.source._serial, ss.pitch_r, ss.pitch_c,
         ss.halo_top, ss.halo_bot, ss.halo_left, ss.halo_right)
        for ss in source_tiles
    )
    cache = plan_cache if plan_cache is not None else PlanCache()

    # --- the shared canonical plan from the ExecutionPlan layer --------------
    # (the only SPMD path: virtual padded tiles make it total over ragged
    # splits and small grids, so there is no legacy closure to fall back to)
    tile_fn, desc = _unified_tile_fn(
        pipeline, mapper, grid, out_info, tile_by_source, cache,
        describe_virtual,
    )
    return TilePlan(
        grid=grid,
        tile_rows=Hr,
        tile_cols=Wc,
        out_info=out_info,
        source_tiles=source_tiles,
        fn=tile_fn,
        unified=True,
        plan_signature=desc.signature,
        pad_rows=pad_rows,
        pad_cols=pad_cols,
        program_key=(
            "spmd", axis_name, nr, nc, Hr, Wc, geom, desc.signature,
        ),
    )


def build_strip_plan(
    pipeline: Pipeline,
    mapper: Mapper,
    n_workers: int,
    axis_name: str = "workers",
    plan_cache: Optional[PlanCache] = None,
) -> TilePlan:
    """The 1-D strip plan: exactly :func:`build_tile_plan` on the
    ``(n_workers, 1)`` grid."""
    return build_tile_plan(
        pipeline, mapper, (n_workers, 1), axis_name, plan_cache=plan_cache
    )


# ---------------------------------------------------------------------------
# the distributed executor
# ---------------------------------------------------------------------------
def _combine_collective(red: Reduction, val, axis_name):
    """``axis_name`` may be one mesh axis or a tuple of axes (the 2-D grid
    reduces over both at once)."""
    if red.kind == "sum":
        return lax.psum(val, axis_name)
    if red.kind == "max":
        return lax.pmax(val, axis_name)
    if red.kind == "min":
        return lax.pmin(val, axis_name)
    if red.kind == "concat":
        return lax.all_gather(val, axis_name).reshape((-1,) + tuple(val.shape[1:]))
    raise ValueError(red.kind)


class ParallelExecutor:
    """Distribute one pipeline over a 2-D device mesh (paper §II.C.2).

    ``grid=(nr, nc)`` lays ``nr × nc == len(devices)`` devices out as a tile
    grid; the default ``(n, 1)`` reproduces the 1-D strip decomposition."""

    def __init__(
        self,
        pipeline: Pipeline,
        mapper: Mapper,
        devices: Optional[Sequence] = None,
        axis_name: str = "workers",
        plan_cache: Optional[PlanCache] = None,
        grid: Optional[Tuple[int, int]] = None,
    ):
        self.pipeline = pipeline
        self.mapper = mapper
        self.devices = list(devices if devices is not None else jax.devices())
        self.axis_name = axis_name
        self.col_axis_name = axis_name + "_cols"
        self.n = len(self.devices)
        if grid is None:
            grid = (self.n, 1)
        nr, nc = grid
        if nr * nc != self.n:
            raise ValueError(
                f"grid {nr}×{nc} needs {nr * nc} devices, got {self.n}"
            )
        self.grid = (nr, nc)
        # the shared ExecutionPlan registry: pass the one the streaming
        # executor used and matching tile geometry becomes a registry hit
        self.plan_cache = plan_cache if plan_cache is not None else PlanCache()
        self.plan = build_tile_plan(
            pipeline, mapper, self.grid, axis_name, plan_cache=self.plan_cache
        )
        self.mesh = Mesh(
            np.array(self.devices).reshape(nr, nc),
            (axis_name, self.col_axis_name),
        )

    # -- global input staging --------------------------------------------------
    def _padded_global(self, ss: SourceTile) -> np.ndarray:
        """Materialize a source and edge-pad it to nr × pitch_r rows and
        nc × pitch_c cols."""
        nr, nc = self.grid
        info = self.pipeline.info(ss.source)
        arr = np.asarray(ss.source.generate(info.full_region))
        if arr.ndim == 2:
            arr = arr[..., None]
        want_r, want_c = nr * ss.pitch_r, nc * ss.pitch_c
        if want_r < arr.shape[0] or want_c < arr.shape[1]:
            raise NotTileParallelizable(
                f"{ss.source.name}: pitch×grid ({want_r}×{want_c}) < image "
                f"{arr.shape[0]}×{arr.shape[1]}"
            )
        pads = (want_r - arr.shape[0], want_c - arr.shape[1])
        if pads != (0, 0):
            arr = np.pad(
                arr, [(0, pads[0]), (0, pads[1]), (0, 0)], mode="edge"
            )
        return arr

    def build_spmd(self):
        """Return (jitted SPMD callable, list of global input arrays)."""
        plan = self.plan
        ar, ac = self.axis_name, self.col_axis_name
        nr, nc = self.grid
        ids = [id(ss.source) for ss in plan.source_tiles]
        halos = {
            id(ss.source): (ss.halo_top, ss.halo_bot, ss.halo_left, ss.halo_right)
            for ss in plan.source_tiles
        }
        persistent = self.pipeline.persistent_nodes()
        reds = {p.name: p.state_reductions for p in persistent}

        def worker(*shards):
            idx = lax.axis_index(ar) * nc + lax.axis_index(ac)
            local = {}
            for sid, x in zip(ids, shards):
                ht, hb, hl, hr = halos[sid]
                x = halo_exchange_rows(x, ht, hb, ar, nr)
                local[sid] = halo_exchange_cols(x, hl, hr, ac, nc)
            out, pstates = plan.fn(local, idx)
            agg = {
                name: {
                    k: _combine_collective(reds[name][k], v, (ar, ac))
                    for k, v in st.items()
                }
                for name, st in pstates.items()
            }
            return out, agg

        in_specs = tuple(P(ar, ac, None) for _ in ids)
        out_specs = (P(ar, ac, None), P())  # states fully reduced → replicated

        def make_program():
            # check_vma=False: the worker body may trace a pallas_call (the
            # plan-layer Pallas fast path), whose outputs carry no
            # varying-manual-axes annotation.  The skipped check only guards
            # the claim that P() outputs are replicated — ours come from
            # psum-style collectives in _combine_collective, so it holds by
            # construction.
            fn = jax.shard_map(
                worker, mesh=self.mesh, in_specs=in_specs,
                out_specs=out_specs, check_vma=False,
            )
            return jax.jit(fn)

        # the jitted SPMD program lives in the shared registry too: a second
        # executor on the same pipeline/geometry/devices reuses one program
        key = self.plan.program_key + (tuple(d.id for d in self.devices),)
        jitted = self.plan_cache.get_or_build(key, make_program)
        # place each source's tiles on their own devices up front (one
        # transfer per shard, never the whole scene through one device)
        tiled = NamedSharding(self.mesh, P(ar, ac, None))
        globals_ = [
            jax.device_put(self._padded_global(ss), tiled)
            for ss in plan.source_tiles
        ]
        return jitted, globals_

    def run(self, keep_outputs: bool = False):
        from repro.core.streaming import StreamResult  # cycle-free local import

        fn, globals_ = self.build_spmd()
        out, agg = fn(*globals_)
        info = self.plan.out_info
        # crop the virtual row/col padding before the write stage
        out = np.asarray(out)[: info.rows, : info.cols]
        nr, nc = self.grid
        Hr, Wc = self.plan.tile_rows, self.plan.tile_cols
        self.mapper.begin(info)
        outputs = []
        for ti in range(nr):
            r0, r1 = ti * Hr, min((ti + 1) * Hr, info.rows)
            if r0 >= r1:
                continue
            for tj in range(nc):
                c0, c1 = tj * Wc, min((tj + 1) * Wc, info.cols)
                if c0 >= c1:
                    continue
                region = ImageRegion((r0, c0), (r1 - r0, c1 - c0))
                data = out[r0:r1, c0:c1]
                self.mapper.consume(region, data)
                if keep_outputs:
                    outputs.append(data)
        presults = {
            p.name: p.synthesize(agg[p.name])
            for p in self.pipeline.persistent_nodes()
        }
        self.mapper.end()
        return StreamResult(
            regions_processed=self.n,
            pixels_processed=info.rows * info.cols,
            persistent_results=presults,
            outputs=outputs if keep_outputs else None,
            cache_stats=self.plan_cache.stats,
        )

    def lower(self):
        """Lower the SPMD program without running (dry-run path)."""
        fn, globals_ = self.build_spmd()
        args = [jax.ShapeDtypeStruct(g.shape, g.dtype) for g in globals_]
        with self.mesh:
            return fn.lower(*args)
