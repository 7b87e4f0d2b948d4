"""P6, conversion: 12-bit DNs rescaled linearly from [0, 4096) to uint8,
truncated.  No kernel runs; this pipeline is the benchmark's control for
the read and write layers.

Work per output pixel: each band read once at its stored dtype and written
once as uint8, ``bands * (itemsize + 1)`` bytes (12 B for four uint16
bands), and five operations a band (subtract, divide, multiply, add, clip).

The reference applies the same rescale to the seeded DNs.  It imports
nothing of the program.
"""
from __future__ import annotations

import functools

import numpy as np

RASTERS = ("scene",)
CHECK = "mismatched_values"
KERNELS = {}


def build(sources, params, mapper_factory):
    from repro import pipelines as PP

    if (params["in_min"], params["in_max"], params["out_dtype"]) != (0, 4096, "uint8"):
        raise ValueError("the P6 builder converts 0..4096 to uint8 only")
    return PP.p6_conversion(sources["scene"], mapper_factory=mapper_factory)


def work(pair, params):
    p, m = pair
    src = p.sources()[0]
    info = p.info(src)
    out = p.info(m)
    per_band = np.dtype(info.dtype).itemsize + np.dtype(out.dtype).itemsize
    return 5.0 * info.bands, float(info.bands * per_band)


@functools.lru_cache(maxsize=None)
def _convert_fn(lo, hi, dtype_name):
    import jax
    import jax.numpy as jnp

    from check import rounder

    rd = rounder(dtype_name)

    def fn(x):
        y = rd(rd(rd(x.astype(jnp.float32)) - lo) / (hi - lo))
        y = jnp.clip(rd(y * 255.0), 0, 255)
        return jnp.floor(y).astype(jnp.uint8)

    return jax.jit(fn)


def reference(gens, r0, r1, params, dtype="float32"):
    fn = _convert_fn(params["in_min"], params["in_max"], dtype)
    return np.asarray(fn(gens["scene"].rows(r0, r1)))


def compare(got, want):
    from check import mismatches

    return mismatches(got, want)
