"""Quickstart: build and run a geospatial pipeline in ~40 lines.

    PYTHONPATH=src python examples/quickstart.py

Shows the paper's three ideas end to end: a process-object graph
(source → filter → persistent filter → mapper), a splitting strategy, and
bounded-memory streamed execution producing the same pixels as a
whole-image run.
"""
import numpy as np

from repro import pipelines as PP
from repro.compile_cache import enable_compile_cache
from repro.core import AutoSplitter, Pipeline
from repro.filters import BandStatistics, ndvi
from repro.raster import MemoryMapper, SyntheticScene

enable_compile_cache()

# 1. wire the graph: 4-band synthetic Spot6-like scene → NDVI → stats → sink
p = Pipeline()
scene = p.add(SyntheticScene(rows=512, cols=512, bands=4, dtype=np.float32))
index = p.add(ndvi(red_band=0, nir_band=3), [scene])
stats = p.add(BandStatistics(bands=1), [index])
sink = p.add(MemoryMapper(), [stats])

# 2. choose the splitting strategy from a memory budget (paper §II.D):
#    stream the image through the pipeline in ~256 KiB regions
splitter = AutoSplitter(memory_budget_bytes=256 * 1024, n_workers=1)

# 3. execute through the unified runner (any executor, one plan registry);
#    a prebuilt (pipeline, mapper) pair goes in as-is — sources and sinks
#    are protocol objects, so a file path or ndarray would work here too
result, _ = PP.run_pipeline((p, sink), splitter=splitter)
ndvi_img = sink.result[..., 0]
s = result.persistent_results["BandStatistics"]

print(f"streamed {result.regions_processed} regions, "
      f"{result.pixels_processed:,} pixels")
print(f"NDVI range [{float(s['min'][0]):+.3f}, {float(s['max'][0]):+.3f}], "
      f"mean {float(s['mean'][0]):+.3f} ± {float(s['std'][0]):.3f}")

# 4. the paper's invariant: streaming == whole-image execution
whole = np.asarray(p.pull(sink, p.info(sink).full_region))[..., 0]
np.testing.assert_allclose(ndvi_img, whole, rtol=1e-5, atol=1e-5)
print("streamed output identical to whole-image execution ✓")
