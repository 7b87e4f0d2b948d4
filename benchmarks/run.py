"""Benchmark harness — one module per paper table/figure + roofline.

    PYTHONPATH=src python -m benchmarks.run [--only io,pipelines,...]
                                            [--snapshot BENCH.json]

Prints ``name,us_per_call,derived`` CSV rows (derived: speedup for I/O,
partition efficiency for pipelines, makespan ratio for balancing,
pipelined/barrier wall ratio for the orchestrator, Mpixel/s-Mtoken/s for
kernels, roofline fraction for the dry-run cells).
Section order follows ``--only``, so consumers must key on row *names*, not
on row positions.

``--snapshot PATH`` additionally writes a machine-readable JSON perf
snapshot (every row, plus the headline plan-layer metrics: describe-pass
hit cost, lower/describe cost ratio, streaming speedups, compile counts) —
CI uploads one per run so the perf trajectory accumulates comparable
points across PRs.

A benchmark that raises makes the harness exit non-zero (the CI smoke job
depends on this — a silently-skipped bench reads as "passed"), but the rows
it measured before failing are still printed and snapshotted when the
exception carries them as ``partial_rows`` — a late gate failure must not
discard the section's data points.  An unknown
``--only`` section name exits non-zero listing the valid names (with a
did-you-mean hint for near-misses).  The only tolerated skip is the
roofline section, which needs dry-run artifacts that a fresh checkout has
not generated yet; its skip is announced on stderr.
"""
from __future__ import annotations

import argparse
import difflib
import importlib
import json
import sys
import traceback

#: section name -> (module path, callable taking the parsed args)
SECTIONS = {
    "io": ("benchmarks.bench_io", lambda mod, args: mod.run(quick=args.quick)),
    "streaming": (
        "benchmarks.bench_streaming",
        lambda mod, args: mod.run(quick=args.quick),
    ),
    "pipelines": ("benchmarks.bench_pipelines", lambda mod, args: mod.run()),
    "balancing": ("benchmarks.bench_balancing", lambda mod, args: mod.run()),
    "orchestrator": (
        "benchmarks.bench_orchestrator",
        lambda mod, args: mod.run(quick=args.quick),
    ),
    "kernels": ("benchmarks.bench_kernels", lambda mod, args: mod.run()),
    "serving": (
        "benchmarks.bench_serving",
        lambda mod, args: mod.run(quick=args.quick),
    ),
    "roofline": ("benchmarks.bench_roofline", lambda mod, args: mod.run()),
}

#: snapshot headline metrics: key -> (csv row name, which csv column)
_SNAPSHOT_METRICS = {
    "plan_describe_hit_cost_us": ("plan_describe_pass_us", "us_per_call"),
    "plan_lower_over_describe": ("plan_describe_pass_us", "derived"),
    "streaming_compile_count": ("streaming_P2_compiles", "us_per_call"),
    # PR 9 tile-grid column: 2-D tiles vs 1-D strips on a wide image, and the
    # one-compile proof that every tile shares the interior signature
    "streaming_grid_tiles_over_strips": ("streaming_grid_tiles_2d", "derived"),
    "streaming_grid_tile_compiles": ("streaming_grid_tile_compiles", "us_per_call"),
    "orchestrator_pipelined_over_barrier": ("orch_chain_pipelined", "derived"),
    "orchestrator_max_in_flight": ("orch_chain_max_in_flight", "us_per_call"),
    # PR 7 pallas fast path: fused-chain Mpixels/s, pallas-vs-jnp speedup and
    # the TPU-projected roofline fraction for the heaviest kernel
    "kernel_fused_chain_mpix_s": ("kernel_fused_chain_pallas_256", "derived"),
    "kernel_fused_over_jnp": ("kernel_fused_speedup", "derived"),
    "kernel_meanshift_roofline_fraction": ("kernel_meanshift_roofline", "derived"),
    # PR 8 plan-warm tile serving: batched-storm p99 latency + throughput,
    # engine speedup over per-tile pulls, and the zero-lowers warm-up proof
    "serving_p99_batched_us": ("serving_storm_batched_p99", "us_per_call"),
    "serving_tiles_per_sec": ("serving_storm_batched", "derived"),
    "serving_batched_speedup": ("serving_batched_speedup", "derived"),
    "serving_post_warm_lowers": ("serving_first_request_lowers", "derived"),
    # PR 10 cloud-native IO: flat/tiled time ratio for scattered windowed
    # reads (> 1 when the RTIC tile layout beats flat row-segment reads)
    "io_tiled_over_flat": ("io_read_tiled_win", "derived"),
}


def write_snapshot(path: str, rows, sections) -> None:
    """Write the JSON perf snapshot: every CSV row keyed by name, plus the
    headline plan-layer metrics when their rows ran in this invocation."""
    by_name = {
        name: {"us_per_call": us, "derived": derived}
        for name, us, derived in rows
    }
    metrics = {
        key: by_name[row][col]
        for key, (row, col) in _SNAPSHOT_METRICS.items()
        if row in by_name
    }
    with open(path, "w") as f:
        json.dump(
            {"sections": list(sections), "metrics": metrics, "rows": by_name},
            f,
            indent=2,
            sort_keys=True,
        )
        f.write("\n")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=",".join(SECTIONS))
    ap.add_argument(
        "--quick", action="store_true",
        help="fast smoke path (CI): benches that support it skip slow sweeps",
    )
    ap.add_argument(
        "--snapshot", default=None, metavar="PATH",
        help="also write a JSON perf snapshot (rows + headline metrics)",
    )
    args = ap.parse_args(argv)
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    wanted = [w for w in args.only.split(",") if w]
    unknown = [w for w in wanted if w not in SECTIONS]
    if unknown:
        hints = []
        for w in unknown:
            close = difflib.get_close_matches(w, SECTIONS, n=1)
            if close:
                hints.append(f"{w!r} (did you mean {close[0]!r}?)")
            else:
                hints.append(repr(w))
        print(
            f"unknown benchmark section(s) {', '.join(hints)}; "
            f"valid sections: {', '.join(sorted(SECTIONS))}",
            file=sys.stderr,
        )
        return 2

    rows = []
    failures = []
    for name in wanted:
        module_path, invoke = SECTIONS[name]
        try:
            mod = importlib.import_module(module_path)
            rows += invoke(mod, args)
        except Exception as e:
            if name == "roofline":
                # dry-run artifacts may not have been generated yet
                print(f"# roofline skipped: {e}", file=sys.stderr)
                continue
            traceback.print_exc()
            # a gated bench that fails late attaches everything it measured
            # before the gate as ``partial_rows`` — harvest them so the CSV
            # and the JSON snapshot still carry the section's data points
            rows += list(getattr(e, "partial_rows", ()) or ())
            failures.append((name, e))

    print("name,us_per_call,derived")
    for name, us, derived in rows:
        print(f"{name},{us:.1f},{derived:.4f}")
    if args.snapshot:
        write_snapshot(args.snapshot, rows, wanted)
    if failures:
        for name, e in failures:
            print(f"# FAILED {name}: {e!r}", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
