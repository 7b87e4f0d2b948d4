"""Pallas TPU kernel: mean-shift mode-search filtering (paper pipeline P5).

The jnp reference sweeps the (2hs+1)² shifted windows of the whole region
through HBM on every iteration.  The kernel keeps only the running
numerator/denominator in VMEM and re-slices the haloed tile per offset, so
HBM traffic is O(1) per pixel instead of O(n_iter·K).  Both accumulate in
the same order (offsets row-major, bands in order).  All iterations run on one resident
tile — arithmetic intensity scales with n_iter·K while bytes stay constant,
pushing the op from memory-bound to compute-bound on TPU.

The band-last input block pads its band axis to 128 lanes in VMEM,
(16+6)·(128+6)·128·4B ≈ 1.5 MB per buffer at the default (16, 128) tile;
the kernel computes on per-band (th, tw) planes and writes a band-planar
(B, th, tw) output block.
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


def _ms_kernel(x_ref, out_ref, *, hs, hr, n_iter, tile, pre_fn):
    th, tw = tile
    x = widen_unsigned(x_ref[0])
    if pre_fn is not None:
        # fused upstream pointwise chain, applied on the VMEM tile
        x = pre_fn(x)
    x = x.astype(jnp.float32)  # (th+2hs, tw+2hs, B)
    # band-planar working set: one lane-dense (rows, cols) plane per band,
    # and the band sum of the range distance in band order
    bands = [x[..., b] for b in range(x.shape[-1])]
    hr2 = hr * hr

    def iteration(_, v):
        num = [jnp.zeros((th, tw), jnp.float32) for _ in bands]
        den = jnp.zeros((th, tw), jnp.float32)
        for u in range(2 * hs + 1):
            for w_ in range(2 * hs + 1):
                xw = [xb[u:u + th, w_:w_ + tw] for xb in bands]
                d2 = (xw[0] - v[0]) ** 2
                for a, c in zip(xw[1:], v[1:]):
                    d2 = d2 + (a - c) ** 2
                m = (d2 <= hr2).astype(jnp.float32)
                num = [n + a * m for n, a in zip(num, xw)]
                den = den + m
        return [n / jnp.maximum(den, 1e-12) for n in num]

    # the iteration loop stays rolled: one (2hs+1)² window sweep to compile
    v = [xb[hs:hs + th, hs:hs + tw] for xb in bands]
    v = jax.lax.fori_loop(0, n_iter, iteration, v)
    for b, vb in enumerate(v):
        out_ref[0, b] = vb


@functools.partial(
    jax.jit, static_argnames=("hs", "hr", "n_iter", "tile", "interpret", "pre_fn")
)
def meanshift(
    x: jnp.ndarray,
    hs: int = 3,
    hr: float = 100.0,
    n_iter: int = 4,
    tile: Tuple[int, int] = (16, 128),
    interpret: Optional[bool] = None,
    pre_fn=None,
) -> jnp.ndarray:
    """x: (H + 2hs, W + 2hs, Bin) pre-padded → (H, W, B).

    ``pre_fn`` (static) is the plan layer's fused pointwise chain, applied
    to the raw haloed tiles inside the kernel; B = Bin without it."""
    if interpret is None:
        interpret = interpret_default()
    H, W, Bin = x.shape[0] - 2 * hs, x.shape[1] - 2 * hs, x.shape[2]
    if pre_fn is not None:
        B = jax.eval_shape(
            pre_fn, jax.ShapeDtypeStruct(x.shape, x.dtype)
        ).shape[-1]
    else:
        B = Bin
    th = min(tile[0], max(8, H))
    tw = min(tile[1], max(8, W))
    Hp, Wp = -(-H // th) * th, -(-W // tw) * tw
    xp = jnp.pad(x, [(0, Hp - H), (0, Wp - W), (0, 0)], mode="edge")
    tiles = extract_patches(xp, (th, tw), hs)
    ntr, ntc = tiles.shape[:2]
    tiles = tiles.reshape(ntr * ntc, th + 2 * hs, tw + 2 * hs, Bin)

    kernel = functools.partial(
        _ms_kernel, hs=hs, hr=hr, n_iter=n_iter, tile=(th, tw), pre_fn=pre_fn
    )
    out = pl.pallas_call(
        kernel,
        grid=(ntr * ntc,),
        in_specs=[
            pl.BlockSpec((1, th + 2 * hs, tw + 2 * hs, Bin), lambda i: (i, 0, 0, 0))
        ],
        out_specs=pl.BlockSpec((1, B, th, tw), lambda i: (i, 0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((ntr * ntc, B, th, tw), jnp.float32),
        interpret=interpret,
        name="meanshift_mode_search",
    )(tiles)
    return stitch_planar(out, ntr, ntc, H, W)
