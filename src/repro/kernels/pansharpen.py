"""Pallas TPU kernel: fused RCS pansharpening (paper pipeline P3).

Fuses the PAN box smoothing, the ratio, and the per-band multiply into one
VMEM pass — the unfused jnp path materializes smooth(PAN) and the ratio in
HBM (3 extra full-image round trips).  The box sum adds the shifted
windows in the jnp reference's order, so the two agree bit for bit.

Band-last input blocks pad their band axis to 128 lanes in VMEM: at the
default (16, 128) tile, xs 16·128·128·4B = 1 MB and pan (16+4)·(128+4)·
128·4B ≈ 1.4 MB per buffer.  The output block is band-planar, (B, th, tw).
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


def _ps_kernel(xs_ref, pan_ref, out_ref, *, radius, tile, pre_xs, pre_pan):
    th, tw = tile
    k = 2 * radius + 1
    # fused pre-stages: upstream pointwise chains run on the VMEM tiles; the
    # PAN band is selected here (after the chain), so the raw multiband tile
    # streams in once and nothing intermediate touches HBM
    pan = widen_unsigned(pan_ref[0])
    if pre_pan is not None:
        pan = pre_pan(pan)
    pan = pan[..., 0].astype(jnp.float32)  # (th+2r, tw+2r)
    xs = widen_unsigned(xs_ref[0])
    if pre_xs is not None:
        xs = pre_xs(xs)
    xs = xs.astype(jnp.float32)  # (th, tw, B)
    # box filter via shifted accumulation (static loop, register-friendly)
    acc = jnp.zeros((th, tw), jnp.float32)
    for u in range(k):
        for v in range(k):
            acc = acc + pan[u:u + th, v:v + tw]
    smooth = acc / (k * k)
    center = pan[radius:radius + th, radius:radius + tw]
    ratio = center / jnp.maximum(smooth, 1e-6)
    # band-planar output block: one lane-dense (th, tw) plane per band
    for b in range(xs.shape[-1]):
        out_ref[0, b] = xs[..., b] * ratio


@functools.partial(
    jax.jit, static_argnames=("radius", "tile", "interpret", "pre_xs", "pre_pan")
)
def pansharpen(
    xs_up: jnp.ndarray,
    pan: jnp.ndarray,
    radius: int = 2,
    tile: Tuple[int, int] = (16, 128),
    interpret: Optional[bool] = None,
    pre_xs=None,
    pre_pan=None,
) -> jnp.ndarray:
    """xs_up: (H, W, Bin); pan: (H + 2r, W + 2r, Bp) pre-padded → (H, W, B).

    ``pre_xs`` / ``pre_pan`` are the plan layer's fused pointwise chains
    (static arguments), applied to the raw input tiles inside the kernel;
    the PAN *band selection* also happens in-kernel (after ``pre_pan``), so
    ``pan`` keeps its band axis.  Without chains B = Bin and Bp may be 1."""
    if interpret is None:
        interpret = interpret_default()
    H, W, Bin = xs_up.shape
    if pre_xs is not None:
        B = jax.eval_shape(
            pre_xs, jax.ShapeDtypeStruct(xs_up.shape, xs_up.dtype)
        ).shape[-1]
    else:
        B = Bin
    Bp = pan.shape[-1]
    th = min(tile[0], max(8, H))
    tw = min(tile[1], max(8, W))
    Hp, Wp = -(-H // th) * th, -(-W // tw) * tw
    xs_p = jnp.pad(xs_up, [(0, Hp - H), (0, Wp - W), (0, 0)], mode="edge")
    pan_p = jnp.pad(pan, [(0, Hp - H), (0, Wp - W), (0, 0)], mode="edge")
    xs_tiles = extract_patches(xs_p, (th, tw), 0)
    pan_tiles = extract_patches(pan_p, (th, tw), radius)
    ntr, ntc = xs_tiles.shape[:2]
    xs_tiles = xs_tiles.reshape(ntr * ntc, th, tw, Bin)
    pan_tiles = pan_tiles.reshape(
        ntr * ntc, th + 2 * radius, tw + 2 * radius, Bp
    )

    kernel = functools.partial(
        _ps_kernel, radius=radius, tile=(th, tw), pre_xs=pre_xs, pre_pan=pre_pan
    )
    out = pl.pallas_call(
        kernel,
        grid=(ntr * ntc,),
        in_specs=[
            pl.BlockSpec((1, th, tw, Bin), lambda i: (i, 0, 0, 0)),
            pl.BlockSpec(
                (1, th + 2 * radius, tw + 2 * radius, Bp),
                lambda i: (i, 0, 0, 0),
            ),
        ],
        out_specs=pl.BlockSpec((1, B, th, tw), lambda i: (i, 0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((ntr * ntc, B, th, tw), jnp.float32),
        interpret=interpret,
        name="pansharpen_rcs",
    )(xs_tiles, pan_tiles)
    return stitch_planar(out, ntr, ntc, H, W)
