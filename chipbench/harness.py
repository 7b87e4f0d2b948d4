"""One run of one cell: set-up, the measured window, the output check, and
(with ``trace``) the per-layer metrics from the profiler's trace.

Everything a cell needs is found by name from ``BENCHMARK.json``:

- the workload entry gives the configuration and the traffic;
- ``configs[].file`` holds the deployment: its rasters, storage tiling and
  executor layout (``executor``, ``stripe_rows`` or ``grid``);
- ``chipbench/traffic/<traffic>.json`` holds the job: which pipeline runs
  (``pipeline``), its parameters, and the output check (name, limit, rows
  per compared window, number of random windows);
- ``chipbench/pipelines/<pipeline>.py`` builds the job through
  ``repro.pipelines`` and holds its work count and plain reference;
- ``chipbench/metrics/<metric>.py`` reads one per-layer metric.

A cell added as those files plus a ``BENCHMARK.json`` entry runs with no
edit here.
"""
from __future__ import annotations

import gc
import importlib.util
import json
import shutil
import sys
import time
from pathlib import Path
from typing import List, Optional


def load_module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or not path.exists():
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Cell:
    """One workload of ``BENCHMARK.json`` with its files resolved."""

    def __init__(self, root: Path, name: str):
        self.root = Path(root)
        self.spec = json.loads((self.root / "BENCHMARK.json").read_text())
        by_name = {w["name"]: w for w in self.spec["workloads"]}
        if name not in by_name:
            raise KeyError(f"no workload {name!r} in BENCHMARK.json")
        self.workload = by_name[name]
        self.name = name
        cfg = {c["name"]: c for c in self.spec["configs"]}[self.workload["config"]]
        self.config = json.loads((self.root / cfg["file"]).read_text())
        self.config["name"] = cfg["name"]
        bench = self.root / "chipbench"
        self.traffic = json.loads(
            (bench / "traffic" / f"{self.workload['traffic']}.json").read_text()
        )
        self.pipe = load_module(
            bench / "pipelines" / f"{self.traffic['pipeline']}.py",
            f"chipbench_pipeline_{self.traffic['pipeline']}",
        )
        self.chips = int(self.workload["chips"])

    def per_layer(self) -> List[dict]:
        """Per-layer metrics this cell reports: those that list it, and
        those without a list (every cell reports every end-to-end metric,
        so such a metric is due in every cell)."""
        return [
            m for m in self.spec["per_layer"]
            if "workloads" not in m or self.name in m["workloads"]
        ]

    def metric_reader(self, name: str):
        return load_module(
            self.root / "chipbench" / "metrics" / f"{name}.py",
            f"chipbench_metric_{name}",
        )


class Context:
    """What a per-layer metric reader may read."""

    def __init__(self, **kw):
        self.__dict__.update(kw)

    def least_seconds(self, work) -> float:
        """Least time the window's pixels take at one chip's peaks, for the
        work per output pixel ``(operations, bytes)``."""
        ops, byts = work
        px = self.pixels_in_window
        return max(px * ops / self.peaks["flops_per_s"],
                   px * byts / self.peaks["bytes_per_s"])

    def kernel_roofline(self, kernel: str) -> Optional[float]:
        """Least time of the kernel's work on the window's pixels (each
        output pixel passes through it once) over its device time summed
        over chips; nothing when the cell has no such kernel.  A trace that
        holds none of its events raises ``MissingEvents``, which ends the
        run without a result."""
        if kernel not in self.kernel_work:
            return None
        secs, _ = self.trace.kernel_seconds(kernel, self.window)
        return 100.0 * self.least_seconds(self.kernel_work[kernel]) / secs

    def span_share(self, kind: str) -> float:
        from devtrace import merge, total

        lo, hi = self.host_window
        return 100.0 * total(merge(self.spans.of(kind), (lo, hi))) / (hi - lo)


def _warm(cell: Cell, pair, cache, splitter, devices) -> None:
    """Compile and run once every program the window will use, without
    touching the sink: the strip plan for the first, an interior and the last
    strip (their reads pad differently), or the tile-grid program."""
    import jax

    p, m = pair
    layout = cell.config
    if layout["executor"] == "spmd":
        from repro.core.parallel import ParallelExecutor

        ex = ParallelExecutor(p, m, devices=devices, plan_cache=cache,
                              grid=tuple(layout["grid"]))
        fn, placed = ex.build_spmd()
        jax.block_until_ready(fn(*placed))
        return
    regions = splitter.split(p.info(m).full_region, p.info(m))
    for r in (regions[0], regions[len(regions) // 2], regions[-1]):
        cache.warm(p, m, [r], virtual=p.virtual_describe_mode())


def _run_pass(cell: Cell, pair, cache, splitter):
    from repro import pipelines as PP

    layout = cell.config
    if layout["executor"] == "spmd":
        return PP.run_pipeline(pair, executor="spmd", plan_cache=cache,
                               grid=tuple(layout["grid"]))
    return PP.run_pipeline(pair, executor="streaming", plan_cache=cache,
                           splitter=splitter)


class Run:
    """One run of one cell, phase by phase: ``setup``, ``window``,
    ``free``, ``check``, ``result``.  ``run`` below strings them together;
    the calibration script calls ``check`` a second time with the control."""

    def __init__(self, root: Path, name: str, seed: int, devices, t_start: float):
        self.cell = Cell(root, name)
        self.root, self.name, self.seed = Path(root), name, seed
        self.devices = list(devices)[: self.cell.chips]
        self.bench = self.root / "chipbench"
        self.t_start = t_start
        self.check_s = None

    def setup(self) -> None:
        """Stored scene, pipeline and warm programs; ends ``setup_s``.

        Writing a seed's stored scene, which only the first run of that
        seed in a checkout does, is timed apart (``scene_s``) and left out
        of ``setup_s``, as the reference's seconds are: otherwise
        ``setup_s`` would tell whether the scene was there before, not how
        long the program takes to set up."""
        from repro.core import PlanCache, StripeSplitter
        from scene import ensure_scene
        from timed import Spans, TimedSink, TimedSource

        cell = self.cell
        t = time.perf_counter()
        paths = {r: ensure_scene(self.root, cell.config, r, self.seed)
                 for r in cell.pipe.RASTERS}
        self.scene_s = time.perf_counter() - t
        self.spans = Spans()
        self.sources = {r: TimedSource(str(paths[r]), self.spans) for r in paths}
        self.sink = TimedSink(self.spans, tile=int(cell.config["storage_tile"]))
        self.pair = cell.pipe.build(self.sources, cell.traffic["params"],
                                    lambda: self.sink)
        p, m = self.pair
        self.info = p.info(m)
        self.cache = PlanCache()
        self.splitter = None
        if cell.config["executor"] == "streaming":
            self.splitter = StripeSplitter(stripe_rows=int(cell.config["stripe_rows"]))
        _warm(cell, self.pair, self.cache, self.splitter, self.devices)
        params = cell.traffic["params"]
        self.step_work = cell.pipe.work(self.pair, params)
        self.kernel_work = {k: f(self.pair, params) for k, f in cell.pipe.KERNELS.items()}
        self.setup_s = time.perf_counter() - self.t_start - self.scene_s

    def window(self, seconds: float, trace: bool) -> None:
        """Whole scene passes, back to back, until ``seconds`` have passed:
        the window ends with the pass that is running then."""
        import jax

        self.trace = trace
        self.trace_dir = self.bench / "traces" / self.name
        if trace:
            shutil.rmtree(self.trace_dir, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(str(self.trace_dir), profiler_options=opts)
        plans0 = self.cache.stats.compiles
        sink, self.passes = self.sink, 0
        n0 = len(sink.commits)
        bytes0 = sink.bytes_written
        with self.spans.span("window"):
            self.t0 = time.perf_counter()
            while self.passes == 0 or time.perf_counter() - self.t0 < seconds:
                with self.spans.span("pass"):
                    _run_pass(self.cell, self.pair, self.cache, self.splitter)
                self.passes += 1
            self.t1 = time.perf_counter()
        if trace:
            jax.profiler.stop_trace()
        self.compiles_in_window = self.cache.stats.compiles - plans0
        self.in_window = [px for _, px in sink.commits[n0:]]
        self.bytes_in_window = sink.bytes_written - bytes0
        self.peak = max(
            (d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in self.devices
        )

    def free(self) -> None:
        """Drop the program's state before the reference runs."""
        for s in self.sources.values():
            s.close()
        del self.pair, self.cache, self.sources
        gc.collect()

    def check(self, dtype: str = "float32") -> float:
        """The widest gap over the sampled windows between the product file
        of the last pass and the reference; with ``dtype="bfloat16"``, the
        gap of the control (the reference computed in bfloat16) instead."""
        from check import sample_windows
        from scene import RticFile, SceneGen, raster_spec

        cell, info = self.cell, self.info
        check = cell.traffic["check"]
        params = cell.traffic["params"]
        gens = {r: SceneGen(raster_spec(cell.config, r), self.seed)
                for r in cell.pipe.RASTERS}
        nr = cell.config["grid"][0] if cell.config.get("grid") else 1
        seams = [-(-info.rows // nr) * i for i in range(1, nr)]
        product = RticFile(Path(self.sink.path))
        worst = 0.0
        try:
            for r0 in sample_windows(self.seed, info.rows, check["window_rows"],
                                     seams, check["random_windows"]):
                r1 = min(info.rows, r0 + check["window_rows"])
                want = cell.pipe.reference(gens, r0, r1, params)
                if dtype == "float32":
                    got = product.read_rows(r0, r1)
                else:
                    got = cell.pipe.reference(gens, r0, r1, params, dtype=dtype)
                worst = max(worst, cell.pipe.compare(got, want))
        finally:
            product.close()
        return worst

    def result(self, worst: float) -> dict:
        import jax

        from devtrace import Trace

        cell = self.cell
        limit = cell.traffic["check"]["limit"]
        checks = {cell.pipe.CHECK: {"value": worst, "limit": limit}}
        dev = self.devices[0]
        device = {
            "platform": dev.platform, "kind": dev.device_kind,
            "count": len(jax.devices()), "memory_peak_bytes": int(self.peak),
        }
        pixels = float(sum(self.in_window))
        out = {"correct": bool(worst <= limit), "attempted": len(self.in_window),
               "failed": 0}
        if not self.trace:
            values = {"mpx_per_s": pixels / (self.t1 - self.t0) / 1e6,
                      "setup_s": self.setup_s}
            metrics = {
                m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                for m in cell.spec["end_to_end"]
            }
        else:
            peaks = json.loads((self.bench / "peaks.json").read_text())
            if dev.device_kind not in peaks:
                raise KeyError(f"no peaks for device kind {dev.device_kind!r}")
            tr = Trace.from_xplane(_xplane(self.trace_dir))
            window = tr.window("window")
            ctx = Context(
                trace=tr, window=window, host_window=(self.t0, self.t1),
                spans=self.spans, peaks=peaks[dev.device_kind],
                step_work=self.step_work, kernel_work=self.kernel_work,
                pixels_in_window=pixels,
                compiles_in_window=self.compiles_in_window,
                n_chips=len(self.devices),
            )
            metrics = {}
            for m in cell.per_layer():
                v = cell.metric_reader(m["name"]).read(ctx)
                if v is None:
                    print(f"chipbench: {m['name']}: nothing to read", file=sys.stderr)
                    continue
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
            device["window_s"] = window[1] - window[0]
            if dev.platform == "tpu" or tr.devices:  # a CPU trace has no device plane
                device["busy_s"] = tr.busy_s(window)
                out["breakdown"] = {
                    "device_ops": tr.top_ops(window),
                    "idle_gaps": tr.idle_gaps(window),
                }
        out.update(
            metrics=metrics, device=device, passes=self.passes,
            window_s=self.t1 - self.t0, product_bytes=self.bytes_in_window,
            scene_s=self.scene_s, check_s=self.check_s,
        )
        out["checks"] = checks  # the numbers compared come last, each beside its limit
        return out


def run(root: Path, name: str, seed: int, seconds: float, trace: bool,
        devices, t_start: float) -> dict:
    """One run of one cell; returns the result line as a dict."""
    r = Run(root, name, seed, devices, t_start)
    r.setup()
    r.window(seconds, trace)
    r.free()
    t = time.perf_counter()
    try:
        worst = r.check()
    finally:
        r.sink.close()
    r.check_s = time.perf_counter() - t
    return r.result(worst)


def _xplane(trace_dir: Path) -> Path:
    found = sorted(Path(trace_dir).glob("plugins/profile/*/*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]
