#!/usr/bin/env python3
"""Chip smoke test: the raster pipelines on a TPU, with compiled Pallas kernels.

    python chip_smoke.py             # one chip: P2, P5, P6 on a Sentinel-2
                                     # tile strip and P3 on a SPOT-6 square,
                                     # each streamed and checked against the
                                     # jnp reference (P6: the eager oracle)
    python chip_smoke.py --chips 4   # 2x2 mesh: the tile-grid SPMD executor
                                     # against one-chip streaming, bit for bit

Runs through the user entry point ``repro.pipelines.run_pipeline``.  It
fails before any work unless JAX's first device is a TPU, fails when a
kernel phase's plan did not take the compiled Pallas kernel, and fails when
a streamed strip reaches the sink as anything but an array of the sink's
dtype with C-ordered rows.  Any failed check exits non-zero.  The last line
of standard output is one JSON object naming the device; the lines above it are smoke timings (one cold run with
compiles, one warm run), not benchmark metrics.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time
from pathlib import Path

import numpy as np

SRC = Path(__file__).resolve().parent / "src"

#: per-kernel Pallas-vs-jnp tolerances (None = bit-exact), as documented in
#: tests/test_pallas_plan.py
TOL = {
    "P2": dict(rtol=1e-3, atol=1e-2),
    "P3": None,
    "P5": dict(rtol=1e-4, atol=1e-2),
}

#: scenes: one Sentinel-2 L2A tile wide (10980 px at 10 m, bands B2/B3/B4/B8)
#: and 2048 rows deep; a 12 km SPOT-6 square (XS 6 m, PAN 1.5 m, ratio 4)
S2_ROWS, S2_COLS, S2_BANDS = 2048, 10980, 4
SPOT6_XS = 2048
STRIPE_ROWS = 256


class SmokeFailure(Exception):
    pass


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def max_err(got: np.ndarray, want: np.ndarray) -> float:
    return float(np.max(np.abs(got.astype(np.float64) - want.astype(np.float64))))


def compare(name: str, got: np.ndarray, want: np.ndarray, tol) -> float:
    check(got.shape == want.shape, f"{name}: shape {got.shape} != {want.shape}")
    check(bool(np.isfinite(got).all()), f"{name}: non-finite output")
    err = max_err(got, want)
    if tol is None:
        check(np.array_equal(got, want), f"{name}: not bit-exact (max err {err})")
    else:
        np.testing.assert_allclose(
            got.astype(np.float64), want.astype(np.float64), err_msg=name, **tol
        )
    return err


def guard_layout(name: str, pair) -> None:
    """Check every strip that reaches the sink: a host array in the sink's
    dtype whose rows are C-ordered, which the writer stores from views.  A
    strip in the TPU's own layout would pass every CPU test and cost the
    writer a host interleave."""
    p, m = pair
    dtype = np.dtype(p.info(m).dtype)
    consume = m.consume

    def checked(region, data):
        ok = isinstance(data, np.ndarray) and data[0].flags.c_contiguous
        check(ok and data.dtype == dtype,
              f"{name}: the strip at {region.index} reached the sink as a "
              f"{type(data).__name__} of {data.dtype}, not an array of "
              f"{dtype} with C-ordered rows")
        consume(region, data)

    m.consume = checked


def stream(pair, cache, splitter):
    from repro import pipelines as PP

    t0 = time.perf_counter()
    res, mapper = PP.run_pipeline(
        pair, executor="streaming", plan_cache=cache, splitter=splitter
    )
    return res, time.perf_counter() - t0


def report(name: str, shape, res, cold: float, warm: float, err: float) -> None:
    snap = res.cache_stats.snapshot() if res.cache_stats is not None else {}
    print(
        f"{name}: scene {'x'.join(map(str, shape))} "
        f"regions={res.regions_processed} compiles={snap.get('compiles')} "
        f"hits={snap.get('hits')} lowers={snap.get('lowers')} "
        f"cold_s={cold:.3f} warm_s={warm:.3f} max_err_vs_ref={err:.6g} "
        "(smoke timings)",
        flush=True,
    )


def kernel_phase(name: str, build, scene_shape, splitter) -> None:
    """Stream a kernel-backed pipeline twice (cold, warm) on the compiled
    Pallas plan and hold it to the jnp reference pipeline."""
    from repro.core import PlanCache

    p, m = pair = build(None)
    info = p.info(m)
    first = splitter.split(info.full_region, info)[0]
    desc = p.describe_pull(m, first, virtual=p.virtual_describe_mode() or False)
    check(bool(desc.pallas_nodes), f"{name}: the plan did not take the Pallas kernel")

    guard_layout(name, pair)
    cache = PlanCache()
    res, cold = stream(pair, cache, splitter)
    out = m.result
    compiles = cache.stats.compiles
    res, warm = stream(pair, cache, splitter)
    check(cache.stats.compiles == compiles, f"{name}: warm run compiled again")
    check(np.array_equal(m.result, out), f"{name}: warm run differs from cold run")

    ref_pair = build(False)
    ref_desc = ref_pair[0].describe_pull(
        ref_pair[1], first, virtual=ref_pair[0].virtual_describe_mode() or False
    )
    check(not ref_desc.pallas_nodes, f"{name}: the reference took the kernel")
    stream(ref_pair, PlanCache(), splitter)
    err = compare(name, out, ref_pair[1].result, TOL[name])
    report(name, scene_shape, res, cold, warm, err)


def conversion_phase(scene, splitter, workdir: str) -> None:
    """P6, the IO-bound control: stream into an RTIC file, read it back and
    hold it to the eager whole-image oracle."""
    from repro import pipelines as PP
    from repro.core import PlanCache
    from repro.raster import as_sink, as_source

    path = os.path.join(workdir, "p6.rtic")
    pair = PP.p6_conversion(scene, mapper_factory=lambda: as_sink(path))
    guard_layout("P6", pair)
    cache = PlanCache()
    res, cold = stream(pair, cache, splitter)
    res, warm = stream(pair, cache, splitter)
    got = as_source(path).read_region()

    p, m = PP.p6_conversion(scene)
    want = np.asarray(p.pull(m, p.info(m).full_region))
    err = compare("P6", got, want, None)
    info = scene.output_info()
    report("P6", (info.rows, info.cols, info.bands), res, cold, warm, err)


def single_chip(rows: int, cols: int, xs_size: int, stripe_rows: int,
                seed: int, workdir: str) -> None:
    from repro import pipelines as PP
    from repro.core import StripeSplitter
    from repro.raster import SyntheticScene, make_spot6_pair

    splitter = StripeSplitter(stripe_rows=stripe_rows)
    scene = SyntheticScene(rows, cols, bands=S2_BANDS, dtype=np.float32, seed=seed)
    shape = (rows, cols, S2_BANDS)
    kernel_phase("P2", lambda up: PP.p2_textures(scene, use_pallas=up), shape, splitter)
    kernel_phase("P5", lambda up: PP.p5_meanshift(scene, use_pallas=up), shape, splitter)
    conversion_phase(scene, splitter, workdir)
    xs, pan = make_spot6_pair(xs_size, xs_size, seed=seed)
    kernel_phase(
        "P3", lambda up: PP.p3_pansharpening(xs, pan, use_pallas=up),
        (4 * xs_size, 4 * xs_size, 4), splitter,
    )


def grid_phase(name: str, build, splitter, devices) -> None:
    """Run one built pipeline on the 2x2 tile grid and hold it bit for bit
    to one-chip streaming of the same (pipeline, mapper) pair."""
    from repro import pipelines as PP
    from repro.core import PlanCache
    from repro.core.parallel import NotTileParallelizable, ParallelExecutor

    p, m = pair = build()
    cache = PlanCache()
    stream(pair, cache, splitter)
    want = m.result
    try:
        ex = ParallelExecutor(p, m, devices=devices, plan_cache=cache, grid=(2, 2))
    except NotTileParallelizable as e:
        print(f"{name} grid (2, 2): not tile-parallelizable: {e}", flush=True)
        return
    mesh_ids = {d.id for d in ex.mesh.devices.flat}
    check(len(mesh_ids) == 4, f"{name}: mesh spans devices {sorted(mesh_ids)}")
    _, placed = ex.build_spmd()
    for g in placed:
        shard_ids = {s.device.id for s in g.addressable_shards}
        check(shard_ids == mesh_ids,
              f"{name}: input shards on {sorted(shard_ids)}, mesh {sorted(mesh_ids)}")
    del placed
    t0 = time.perf_counter()
    PP.run_pipeline(pair, executor="spmd", grid=(2, 2), plan_cache=cache)
    dt = time.perf_counter() - t0
    got = m.result
    err = compare(f"{name} grid", got, want, None)
    print(
        f"{name} grid (2, 2): devices {sorted(mesh_ids)} "
        f"scene {'x'.join(map(str, got.shape))} "
        f"spmd_s={dt:.3f} max_err_vs_streaming={err:.6g} (smoke timing)",
        flush=True,
    )


def four_chips(rows: int, cols: int, xs_size: int, stripe_rows: int,
               seed: int, devices) -> None:
    from repro import pipelines as PP
    from repro.core import StripeSplitter
    from repro.raster import SyntheticScene, make_spot6_pair

    splitter = StripeSplitter(stripe_rows=stripe_rows)
    scene = SyntheticScene(rows, cols, bands=S2_BANDS, dtype=np.float32, seed=seed)
    grid_phase("P2", lambda: PP.p2_textures(scene), splitter, devices)
    grid_phase("P5", lambda: PP.p5_meanshift(scene), splitter, devices)
    xs, pan = make_spot6_pair(xs_size, xs_size, seed=seed)
    # the executor stages whole tiles: P3's program needs 14.3 GB of
    # temporaries per device at this size, close to a v5e's 16 GB
    grid_phase("P3", lambda: PP.p3_pansharpening(xs, pan), splitter, devices)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: streaming phases on one chip; 4: 2x2 tile grid")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (first device is {dev.platform}); refusing "
              "to run on another backend", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} devices, "
              f"found {len(devices)}", file=sys.stderr)
        return 2
    sys.path.insert(0, str(SRC))
    from repro.compile_cache import enable_compile_cache
    from repro.kernels.ops import resolve_use_pallas
    from repro.kernels.util import interpret_default

    if not resolve_use_pallas(None):
        print("chip_smoke: REPRO_USE_PALLAS disables the kernels", file=sys.stderr)
        return 2
    if interpret_default():
        print("chip_smoke: Pallas would run in interpret mode", file=sys.stderr)
        return 2
    print(f"device: {dev.device_kind} x{len(devices)}; compile cache "
          f"{enable_compile_cache()}", flush=True)
    try:
        if args.chips == 1:
            with tempfile.TemporaryDirectory(prefix="chip_smoke_") as workdir:
                single_chip(S2_ROWS, S2_COLS, SPOT6_XS, STRIPE_ROWS, args.seed, workdir)
        else:
            four_chips(S2_ROWS, S2_COLS, SPOT6_XS, STRIPE_ROWS, args.seed, devices[:4])
    except (SmokeFailure, AssertionError) as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": len(devices),
    }}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
