"""P2, Haralick textures: the cell's build, work count and plain reference.

Work per output pixel (logical shapes and stored dtypes; no padding, halo
copy or layout counts):

- bytes: the one band the textures read, once, at its stored dtype, and the
  five float32 features written once: ``itemsize + 5 * 4`` (22 B for uint16).
- operations: quantize the pixel (3), form the (2r+1)^2 pair codes of the
  window (2 each) and count them into the Q^2 histogram (1 each), fold the
  Q^2 bins into the nine sums the five features need (14 each: p^2,
  p log p, p d^2, p / (1 + d^2), p i, p j, p i^2, p j^2, p i j, with their
  adds), and finish the features (12): ``15 + 3 (2r+1)^2 + 14 Q^2``.

The kernel ``glcm_haralick`` does all of that work, so its count is the
step's count.

The reference quantizes, builds each pixel's co-occurrence counts from
one-hot pair codes, and evaluates the features from their definitions
(variances about the mean, correlation 0 where var_i var_j < 1e-4), with
the image edge replicated as the pipeline's boundary rule says.  It imports
nothing of the program.
"""
from __future__ import annotations

import functools

import numpy as np

RASTERS = ("scene",)
CHECK = "feature_gap"
N_FEATURES = 5


def build(sources, params, mapper_factory):
    from repro import pipelines as PP

    if (tuple(params["offset"]), params["vmin"], params["vmax"]) != ((0, 1), 0, 4096):
        raise ValueError("the P2 builder quantizes 0..4096 at offset (0, 1) only")
    return PP.p2_textures(
        sources["scene"], mapper_factory=mapper_factory,
        radius=params["radius"], levels=params["levels"],
    )


def work(pair, params):
    """(operations, bytes) per output pixel of the whole step."""
    p, m = pair
    src = p.sources()[0]
    in_bytes = np.dtype(p.info(src).dtype).itemsize
    out = p.info(m)
    out_bytes = out.bands * np.dtype(out.dtype).itemsize
    r, q = params["radius"], params["levels"]
    ops = 15 + 3 * (2 * r + 1) ** 2 + 14 * q * q
    return float(ops), float(in_bytes + out_bytes)


KERNELS = {"glcm_haralick": work}


@functools.lru_cache(maxsize=None)
def _features_fn(radius, offset, levels, vmin, vmax, dtype_name):
    import jax
    import jax.numpy as jnp

    from check import rounder

    rd = rounder(dtype_name)
    dr, dc = offset
    halo = radius + max(abs(dr), abs(dc))
    nbins = levels * levels

    def total(v):
        return rd(jnp.sum(rd(v), -1))

    def fn(x):
        h = x.shape[0] - 2 * halo
        w = x.shape[1] - 2 * halo
        xf = rd(x.astype(jnp.float32))
        q = jnp.floor(rd(rd(rd(xf - vmin) / (vmax - vmin)) * levels))
        q = jnp.clip(q, 0, levels - 1).astype(jnp.int32)
        counts = jnp.zeros((h, w, nbins), jnp.float32)  # at most 25: exact
        for u in range(-radius, radius + 1):
            for v in range(-radius, radius + 1):
                a = q[halo + u:halo + u + h, halo + v:halo + v + w]
                b = q[halo + u + dr:halo + u + dr + h, halo + v + dc:halo + v + dc + w]
                counts = counts + jax.nn.one_hot(a * levels + b, nbins)
        p = rd(counts / (2 * radius + 1) ** 2)
        i = (jnp.arange(nbins) // levels).astype(jnp.float32)
        j = (jnp.arange(nbins) % levels).astype(jnp.float32)
        d2 = (i - j) ** 2
        energy = total(p * p)
        entropy = -total(p * rd(jnp.log(jnp.where(p > 0, p, 1.0))))
        contrast = total(p * d2)
        homogeneity = total(p / (1 + d2))
        di = rd(i - total(p * i)[..., None])
        dj = rd(j - total(p * j)[..., None])
        var_i = total(p * rd(di * di))
        var_j = total(p * rd(dj * dj))
        cov = total(p * rd(di * dj))
        denom2 = rd(var_i * var_j)
        corr = jnp.where(
            denom2 < 1e-4, 0.0, rd(cov / rd(jnp.sqrt(jnp.maximum(denom2, 1e-4))))
        )
        return jnp.stack([energy, entropy, contrast, homogeneity, corr], -1)

    return jax.jit(fn)


def reference(gens, r0, r1, params, dtype="float32", block_rows=32):
    """Features of output rows [r0, r1), full width, as float32; every
    operation rounded to ``dtype`` (the control: ``"bfloat16"``)."""
    radius, offset = params["radius"], tuple(params["offset"])
    halo = radius + max(abs(offset[0]), abs(offset[1]))
    fn = _features_fn(radius, offset, params["levels"], params["vmin"],
                      params["vmax"], dtype)
    x = gens["scene"].rows_edge(r0 - halo, r1 + halo, pad_cols=halo)[..., 0]
    out = []
    for a in range(0, r1 - r0, block_rows):
        b = min(a + block_rows, r1 - r0)
        blk = x[a:b + 2 * halo]
        if b - a < block_rows:  # keep one block shape: edge-pad, then crop
            blk = np.pad(blk, [(0, block_rows - (b - a)), (0, 0)], mode="edge")
        out.append(np.asarray(fn(blk))[: b - a])
    return np.concatenate(out, 0)


def compare(got, want):
    from check import rel_gap

    return rel_gap(got, want)
