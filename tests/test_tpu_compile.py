"""Compile the main-path Pallas kernels for a described TPU v5e chip.

Interpret mode on the CPU cannot see what the TPU compiler refuses: value-
level dynamic slices, relayouts it cannot lower, scoped-VMEM overruns.  Each
test compiles one kernel with ``interpret=False`` at its deployment tile size
over one streaming strip of a real scene, for a chip that is described, not
attached.  Nothing runs.  The topology is described inside a module fixture,
so only the worker that runs this file loads the TPU compiler, and JAX's
persistent compile cache is off around the compiles (an entry written for a
described chip cannot be read back without one).
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import glcm, meanshift, pansharpen

#: one 256-row streaming strip: a Sentinel-2 tile width (4 bands) and a
#: SPOT-6 PAN width at the 4x ratio
S2_COLS, SPOT6_PAN_COLS, STRIP = 10980, 8192, 256


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    saved_log_dir = os.environ.get("TPU_LOG_DIR")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    cache_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", cache_on)
    compilation_cache.reset_cache()
    if saved_log_dir is None:
        os.environ.pop("TPU_LOG_DIR", None)


def _compile(fn, sharding, *specs):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=sharding) for s, dt in specs]
    return jax.jit(fn).lower(*args).compile()


def _assert_kernel(compiled, name):
    text = compiled.as_text()
    assert "tpu_custom_call" in text and name in text


#: raw scene dtypes: float32 (the synthetic scenes) and uint16 (sensor DNs,
#: which the kernels widen on load — Mosaic cannot cast uint16 to float)
RAW = pytest.mark.parametrize("raw", [jnp.float32, jnp.uint16])


@RAW
def test_glcm_compiles_for_v5e(one_chip, raw):
    """P2's fused plan body: band selection runs in the kernel."""
    halo = 3  # radius 2 + offset (0, 1)

    def p2_strip(x):
        return glcm.glcm_features(
            x, 2, (0, 1), 8, 0.0, 4096.0, interpret=False,
            pre_fn=lambda t: t[..., 0].astype(jnp.float32),
        )

    compiled = _compile(
        p2_strip, one_chip, ((STRIP + 2 * halo, S2_COLS + 2 * halo, 4), raw)
    )
    _assert_kernel(compiled, "glcm_haralick")


@RAW
def test_meanshift_compiles_for_v5e(one_chip, raw):
    hs = 3

    def p5_strip(x):
        return meanshift.meanshift(x, hs, 120.0, 4, interpret=False)

    compiled = _compile(
        p5_strip, one_chip, ((STRIP + 2 * hs, S2_COLS + 2 * hs, 4), raw)
    )
    _assert_kernel(compiled, "meanshift_mode_search")


@RAW
def test_pansharpen_compiles_for_v5e(one_chip, raw):
    """P3's fuse step: the resampled XS is float32, the PAN is raw."""
    r = 2

    def p3_strip(xs_up, pan):
        return pansharpen.pansharpen(xs_up, pan, r, interpret=False)

    compiled = _compile(
        p3_strip, one_chip,
        ((STRIP, SPOT6_PAN_COLS, 4), jnp.float32),
        ((STRIP + 2 * r, SPOT6_PAN_COLS + 2 * r, 1), raw),
    )
    _assert_kernel(compiled, "pansharpen_rcs")


@pytest.mark.parametrize("bands", [4, 5])
def test_strip_program_hands_pixels_back_lane_dense_for_v5e(one_chip, bands):
    """The plan entry's program for a P6 strip of a Sentinel-2 tile width
    returns its pixels as a 2-D ``(rows, cols' * bands)`` array, ``cols'``
    the columns padded to whole 128-lane rows, which the TPU lays out
    row-major (a rank-3 strip, or an unpadded 2-D one, it lays out rows
    fastest), and holds no more temporaries than the canonical program's
    plus the output (a relayout to one flat row held a lane-padded copy of
    the strip and took minutes to compile)."""
    import numpy as np

    from repro import pipelines as PP
    from repro.core import ImageRegion
    from repro.core.execplan import CacheStats, _CompiledEntry
    from repro.raster.sources import ArraySource

    scene = ArraySource(np.broadcast_to(np.zeros((), np.uint16), (3 * STRIP, S2_COLS, bands)))
    p, m = PP.p6_conversion(scene)
    desc = p.describe_pull(m, ImageRegion((STRIP, 0), (STRIP, S2_COLS)))
    plan = p.lower_pull(desc)
    args = (
        [jax.ShapeDtypeStruct((STRIP, S2_COLS, bands), jnp.uint16, sharding=one_chip)],
        {}, tuple(jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
                  for _ in desc.origin_values),
    )
    entry = _CompiledEntry(plan.canonical_fn, CacheStats())
    dense = entry._jitted.lower(*args).compile()
    canonical = jax.jit(plan.canonical_fn).lower(*args).compile()
    out_bytes = STRIP * 11008 * bands  # 10980 columns padded to 11008
    assert canonical.output_formats[0].layout.major_to_minor != (0, 1, 2)
    assert dense.output_formats[0].layout.major_to_minor == (0, 1)
    assert dense.memory_analysis().output_size_in_bytes == out_bytes
    assert (dense.memory_analysis().temp_size_in_bytes
            <= canonical.memory_analysis().temp_size_in_bytes + out_bytes)
