#!/usr/bin/env python3
"""Readings that a check's limit is set from: the program's gap and the
control's gap on many seeds of one cell, in one process.

    python3 chipbench/calibrate.py --workload s2-textures --seeds 11 12 13

For each seed: set-up as in a run, one scene pass (the shortest window),
then the check on the sampled windows: the product against the reference
(the program's reading) and, on the first ``--control-seeds`` seeds, the
reference computed in bfloat16 against the reference (the control's
reading).  One JSON line per seed, then the largest program reading and
the smallest control reading.  The benchmark's own runs never run the
control.  Needs the cell's chips, like ``run.py``.
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
#: the nearest precision below the configurations' float32
CONTROL = "bfloat16"
ROOT = BENCH.parent


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control-seeds", type=int, default=None,
                    help="run the control on this many of the seeds (default all)")
    args = ap.parse_args(argv)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(BENCH / ".jax_cache")
    import jax

    jax.config.update("jax_compilation_cache_dir", str(BENCH / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    if jax.devices()[0].platform != "tpu":
        print("calibrate: no TPU", file=sys.stderr)
        return 2
    sys.path[:0] = [str(BENCH), str(ROOT / "src")]
    import harness

    prog, ctrl = [], []
    n_control = len(args.seeds) if args.control_seeds is None else args.control_seeds
    for k, seed in enumerate(args.seeds):
        t = time.perf_counter()
        r = harness.Run(ROOT, args.workload, seed, jax.devices(), t)
        r.setup()
        r.window(0.01, False)
        r.free()
        prog.append(r.check())
        r.sink.close()
        if k < n_control:
            ctrl.append(r.check(CONTROL))
        print(json.dumps({
            "seed": seed, "program": prog[-1],
            "control": ctrl[-1] if k < n_control else None,
            "setup_s": r.setup_s, "pass_s": r.t1 - r.t0,
            "seconds": time.perf_counter() - t,
        }), flush=True)
    print(json.dumps({"workload": args.workload, "check": r.cell.pipe.CHECK,
                      "program_max": max(prog), "control_min": min(ctrl, default=None),
                      "seeds": len(prog)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
