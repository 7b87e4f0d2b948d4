#!/usr/bin/env python3
"""Run one benchmark cell once on the chips of this machine.

    python3 chipbench/run.py --workload s2-textures --seed 7 --seconds 10 --trace 0

``--trace 0`` prints the cell's end-to-end metrics; ``--trace 1`` runs the
same window under the profiler and prints its per-layer metrics.  The last
line of standard output is one JSON object (``correct``, ``attempted``,
``failed``, ``metrics``, ``device``, with ``--trace 1`` ``breakdown``, and
last ``checks``: each number the output check compared, with its limit).
The last lines of standard error repeat the checks.

The run refuses, with a non-zero exit and no result, when JAX finds no TPU
or fewer chips than the cell asks for.  JAX's persistent compilation cache
lives in ``chipbench/.jax_cache`` of this checkout.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse(argv)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(BENCH / ".jax_cache")
    import jax

    jax.config.update("jax_compilation_cache_dir", str(BENCH / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    chips = {w["name"]: w["chips"] for w in spec["workloads"]}.get(args.workload)
    if chips is None:
        print(f"chipbench: no workload {args.workload!r}", file=sys.stderr)
        return 2
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chipbench: no TPU (JAX's first device is {devices[0].platform}); "
              "refusing to measure another backend", file=sys.stderr)
        return 2
    if len(devices) < chips:
        print(f"chipbench: {args.workload} needs {chips} chips, JAX finds "
              f"{len(devices)}", file=sys.stderr)
        return 2
    sys.path[:0] = [str(BENCH), str(ROOT / "src")]
    import harness

    result = harness.run(ROOT, args.workload, args.seed, args.seconds,
                         bool(args.trace), devices, T_START)
    for name, c in result["checks"].items():
        print(f"check {name} = {c['value']!r} (limit {c['limit']!r})", file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
