#!/usr/bin/env python3
"""Compile each cell's device program at its real size for a described TPU
v5e (no chip needed) and print ``memory_analysis()``.

    JAX_PLATFORMS=cpu python3 chipbench/compile_for_chip.py [workload ...]

One-chip cells compile their interior strip plan; the tile-grid cell
compiles its shard_map program on a described v5e:2x2.  Sources are
shape-only stand-ins of the configured rasters, so nothing is generated or
read.  The Pallas kernels compile for the TPU, not in interpret mode.  A
compile that passes is not a chip run: it shows the program fits and what
it holds on each chip, nothing about time.
"""
from __future__ import annotations

import json
import os
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


def main(argv) -> int:
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    os.environ["REPRO_USE_PALLAS"] = "1"
    sys.path[:0] = [str(BENCH), str(ROOT / "src")]
    import jax
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    import harness
    from repro.kernels import glcm, meanshift, pansharpen
    from repro.raster.sources import ArraySource
    from scene import raster_spec

    jax.config.update("jax_enable_compilation_cache", False)
    for mod in (glcm, meanshift, pansharpen):
        mod.interpret_default = lambda: False
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    names = argv or [w["name"] for w in spec["workloads"]]
    for name in names:
        cell = harness.Cell(ROOT, name)
        sources = {}
        for r in cell.pipe.RASTERS:
            s = raster_spec(cell.config, r)
            shape = (s["rows"], s["cols"], s["bands"])
            sources[r] = ArraySource(np.broadcast_to(np.zeros((), s["dtype"]), shape))
        from repro.raster import MemoryMapper

        p, m = cell.pipe.build(sources, cell.traffic["params"], MemoryMapper)
        info = p.info(m)
        if cell.config["executor"] == "spmd":
            compiled = _grid_program(p, m, topo, tuple(cell.config["grid"]))
        else:
            one = SingleDeviceSharding(topo.devices[0])
            rows = int(cell.config["stripe_rows"])
            from repro.core.region import ImageRegion

            region = ImageRegion((rows, 0), (rows, info.cols))  # an interior strip
            desc = p.describe_pull(m, region, virtual=p.virtual_describe_mode())
            plan = p.lower_pull(desc)
            arrays = [
                jax.ShapeDtypeStruct(
                    (w or (req.rows, req.cols)) + (p.info(src).bands,),
                    p.info(src).dtype, sharding=one,
                )
                for (src, _, req), w in zip(desc.reads, desc.windows or [None] * len(desc.reads))
            ]
            origins = tuple(jax.ShapeDtypeStruct((), np.int32, sharding=one)
                            for _ in desc.origin_values)
            compiled = jax.jit(plan.canonical_fn).lower(arrays, {}, origins).compile()
        ma = compiled.memory_analysis()
        kernels = [k for k in ("glcm_haralick", "pansharpen_rcs", "meanshift_mode_search")
                   if k in compiled.as_text()]
        print(json.dumps({
            "workload": name,
            "argument_bytes": ma.argument_size_in_bytes,
            "output_bytes": ma.output_size_in_bytes,
            "temp_bytes": ma.temp_size_in_bytes,
            "generated_code_bytes": ma.generated_code_size_in_bytes,
            "kernels": kernels,
        }), flush=True)
    return 0


def _grid_program(p, m, topo, grid):
    """The tile-grid executor's program, with its inputs described on the
    mesh instead of placed (a described chip holds no arrays)."""
    import jax
    from repro.core.parallel import ParallelExecutor

    ex = ParallelExecutor(p, m, devices=topo.devices[: grid[0] * grid[1]], grid=grid)
    real_put = jax.device_put
    jax.device_put = lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s)
    try:
        fn, described = ex.build_spmd()
    finally:
        jax.device_put = real_put
    return fn.lower(*described).compile()


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
