"""Pallas TPU kernel: per-pixel GLCM Haralick features (paper pipeline P2).

Hardware adaptation (GPU→TPU): per-window co-occurrence histograms are
scatter workloads on GPU (atomics into shared-memory bins).  TPUs have no
fast scatter, so the histogram is rebuilt as *vectorized one-hot
counting*: the pair codes ``q1·Q + q2`` of the (2R+1)² window offsets are
computed once per tile, then a loop over the Q² bins counts each bin's
matches and folds its share into the Haralick moments — pure VPU work on
lane-dense (th, tw) planes, no atomics, no gather, and no (th, tw, Q²)
accumulator.

Grid: one program per (tile_r, tile_c) output tile; inputs are pre-tiled
with halos host-side (`kernels.util.extract_patches`), so every block is a
self-contained VMEM working set.  The raw input block is band-last, so its
band axis pads to 128 lanes: (64+6)·(128+6)·128·4B ≈ 4.8 MB per buffer at
the default (64, 128) tile, under the 16 MiB scoped-VMEM default with
double buffering.  The output block is feature-planar, (5, th, tw).
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.util import (
    extract_patches,
    interpret_default,
    stitch_planar,
    widen_unsigned,
)


def _glcm_kernel(x_ref, out_ref, *, radius, offset, levels, vmin, vmax,
                 tile, pre_fn):
    th, tw = tile
    dr, dc = offset
    m = max(abs(dr), abs(dc))
    halo = radius + m
    x = widen_unsigned(x_ref[0])  # raw (th + 2·halo, tw + 2·halo[, B]) tile
    # fused pre-stage: the upstream pointwise chain (and band selection)
    # runs on the VMEM tile, then quantization — all inside the kernel, so
    # neither the chain's intermediates nor the int32 levels ever hit HBM
    band = (pre_fn(x) if pre_fn is not None else x).astype(jnp.float32)
    q = jnp.clip(
        jnp.floor((band - vmin) / max(1e-12, vmax - vmin) * levels),
        0,
        levels - 1,
    ).astype(jnp.int32)

    # pair codes q1·Q + q2 of the (2R+1)² window offsets (static slices)
    codes = [
        q[halo + u:halo + u + th, halo + v:halo + v + tw] * levels
        + q[halo + u + dr:halo + u + dr + th, halo + v + dc:halo + v + dc + tw]
        for u in range(-radius, radius + 1)
        for v in range(-radius, radius + 1)
    ]
    # every offset lands in exactly one bin: the window total is static
    total = float(len(codes))

    def add_bin(b, sums):
        # one co-occurrence bin for the whole tile: its count over the
        # window, then its share of each Haralick moment
        count = (codes[0] == b).astype(jnp.float32)
        for c in codes[1:]:
            count = count + (c == b).astype(jnp.float32)
        p = count / total
        i = (b // levels).astype(jnp.float32)  # bin → row level
        j = (b % levels).astype(jnp.float32)  # bin → col level
        energy, ent, contrast, homog, mu_i, mu_j, s_ii, s_jj, s_ij = sums
        return (
            energy + p * p,
            ent + p * jnp.log(p + 1e-12),
            contrast + p * ((i - j) ** 2),
            homog + p / (1.0 + (i - j) ** 2),
            mu_i + p * i,
            mu_j + p * j,
            s_ii + p * i * i,
            s_jj + p * j * j,
            s_ij + p * i * j,
        )

    zero = jnp.zeros((th, tw), jnp.float32)
    energy, ent, contrast, homog, mu_i, mu_j, s_ii, s_jj, s_ij = (
        jax.lax.fori_loop(0, levels * levels, add_bin, (zero,) * 9)
    )
    var_i = s_ii - mu_i * mu_i
    var_j = s_jj - mu_j * mu_j
    cov = s_ij - mu_i * mu_j
    denom2 = var_i * var_j
    corr = jnp.where(denom2 < 1e-4, 0.0, cov / jnp.sqrt(jnp.maximum(denom2, 1e-4)))
    # feature-planar output block: each feature is a lane-dense (th, tw) plane
    for k, feat in enumerate((energy, -ent, contrast, homog, corr)):
        out_ref[0, k] = feat


@functools.partial(
    jax.jit,
    static_argnames=(
        "radius", "offset", "levels", "vmin", "vmax", "tile", "interpret",
        "pre_fn",
    ),
)
def glcm_features(
    band: jnp.ndarray,
    radius: int = 2,
    offset: Tuple[int, int] = (0, 1),
    levels: int = 8,
    vmin: float = 0.0,
    vmax: float = 4096.0,
    tile: Tuple[int, int] = (64, 128),
    interpret: Optional[bool] = None,
    pre_fn=None,
) -> jnp.ndarray:
    """band: (H + 2·halo, W + 2·halo) float — pre-padded by halo = radius +
    max|offset| (the filter's requested region).  Returns (H, W, 5).

    With ``pre_fn`` (the plan layer's fused pointwise chain, a static
    argument), ``band`` is instead the *raw* upstream array
    (H + 2·halo, W + 2·halo, ...) and ``pre_fn`` maps its haloed tiles to
    the 2-D float band inside the kernel.  Quantization always runs in the
    kernel, so the int32 level image never materializes in HBM."""
    if interpret is None:
        interpret = interpret_default()
    dr, dc = offset
    halo = radius + max(abs(dr), abs(dc))
    H, W = band.shape[0] - 2 * halo, band.shape[1] - 2 * halo
    # tile the padded image; edge-pad ragged tiles (cropped after — edge
    # padding commutes with the kernel's pointwise pre-stage)
    th = min(tile[0], max(8, H))
    tw = min(tile[1], max(8, W))
    Hp = -(-H // th) * th
    Wp = -(-W // tw) * tw
    extra = band.shape[2:]
    xfull = jnp.pad(
        band, [(0, Hp - H), (0, Wp - W)] + [(0, 0)] * len(extra), mode="edge"
    )
    patches = extract_patches(xfull, (th, tw), halo)
    ntr, ntc = patches.shape[:2]
    patches = patches.reshape((ntr * ntc, th + 2 * halo, tw + 2 * halo) + extra)

    kernel = functools.partial(
        _glcm_kernel, radius=radius, offset=offset, levels=levels,
        vmin=vmin, vmax=vmax, tile=(th, tw), pre_fn=pre_fn,
    )
    blk = (1, th + 2 * halo, tw + 2 * halo) + extra
    nd = len(blk)
    out = pl.pallas_call(
        kernel,
        grid=(ntr * ntc,),
        in_specs=[pl.BlockSpec(blk, lambda i, _n=nd: (i,) + (0,) * (_n - 1))],
        out_specs=pl.BlockSpec((1, 5, th, tw), lambda i: (i, 0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((ntr * ntc, 5, th, tw), jnp.float32),
        interpret=interpret,
        name="glcm_haralick",
    )(patches)
    return stitch_planar(out, ntr, ntc, H, W)
