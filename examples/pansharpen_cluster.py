"""End-to-end driver (the paper's production scenario): pansharpen a
synthetic Spot6 product pair and write the result with the strip-parallel
writer — the full P3 pipeline of Table 2.

    PYTHONPATH=src python examples/pansharpen_cluster.py [--xs-rows 512]

With one local device this runs the streamed executor (worker 0 of N); with
multiple devices (XLA_FLAGS=--xla_force_host_platform_device_count=8) it
runs the shard_map cluster executor — one pipeline replica per device, halo
exchange via ppermute, exactly the paper's §II.C.2.
"""
import argparse
import tempfile
import time
from pathlib import Path

import jax
import numpy as np

from repro import pipelines as PP
from repro.compile_cache import enable_compile_cache
from repro.core import StripeSplitter
from repro.raster import as_source, make_spot6_pair


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--xs-rows", type=int, default=256)
    ap.add_argument("--xs-cols", type=int, default=256)
    ap.add_argument("--out", default=None,
                    help="output path (.rtif flat strip-parallel file, or "
                         ".rtic for the tiled pyramidal container)")
    args = ap.parse_args()
    enable_compile_cache()

    out = args.out or str(Path(tempfile.mkdtemp()) / "pansharpened.rtif")
    xs, pan = make_spot6_pair(args.xs_rows, args.xs_cols)
    n_dev = len(jax.devices())

    print(f"product: XS {args.xs_rows}×{args.xs_cols}×4 + PAN "
          f"{args.xs_rows*4}×{args.xs_cols*4}")

    # sources and sinks are protocol objects: `sink=out` picks the writer
    # from the path (.rtic → TileWriter), and the executor choice doesn't
    # change the pixels — one plan registry serves both engines
    t0 = time.time()
    if n_dev > 1:
        print(f"cluster executor on {n_dev} devices (one pipeline replica each)")
        res, _ = PP.run_pipeline("P3", xs, pan, executor="spmd", sink=out)
    else:
        print("streaming executor (single worker)")
        res, _ = PP.run_pipeline(
            "P3", xs, pan, sink=out, splitter=StripeSplitter(n_splits=8)
        )
    dt = time.time() - t0

    mp = res.pixels_processed / 1e6
    print(f"processed {mp:.1f} Mpixels in {dt:.2f}s → {mp/dt:.1f} Mpix/s")
    got = as_source(out).read_region()  # container magic picks the reader
    assert np.isfinite(got).all()
    print(f"wrote {out} ({Path(out).stat().st_size/2**20:.1f} MiB) ✓")


if __name__ == "__main__":
    main()
