"""Shared kernel utilities: host-side tiling with halos, interpret-mode
auto-detection.

TPU Pallas BlockSpecs address non-overlapping blocks; windowed kernels need
overlapping (haloed) tiles.  ``extract_patches`` materializes the overlap
host-side — a (1 + 2·halo/tile)² footprint — so each grid step streams one
self-contained VMEM tile — the paper's "splitting strategy chosen from the
memory specification" (§II.B/D) one level down the memory hierarchy.  The
copy is not cheap on a TPU: a band-last patch pads its 1–5 bands to 128
lanes in HBM, about 36× the input bytes of a strip.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp


def interpret_default() -> bool:
    """Pallas interpret mode on CPU hosts (the validation path); compiled on TPU."""
    return jax.default_backend() != "tpu"


def widen_unsigned(x: jnp.ndarray) -> jnp.ndarray:
    """Mosaic has no unsigned-integer → float conversion: kernels widen a
    raw uint8/uint16 tile (sensor DNs) to int32 on load, which holds every
    value exactly, before any fused chain or cast sees it."""
    if jnp.issubdtype(x.dtype, jnp.unsignedinteger) and x.dtype.itemsize < 4:
        return x.astype(jnp.int32)
    return x


def pad_to_multiple(x: jnp.ndarray, mult_r: int, mult_c: int, mode: str = "edge"):
    """Pad rows/cols (leading two axes) up to multiples; returns (padded, r, c)."""
    r = (-x.shape[0]) % mult_r
    c = (-x.shape[1]) % mult_c
    if r or c:
        x = jnp.pad(x, [(0, r), (0, c)] + [(0, 0)] * (x.ndim - 2), mode=mode)
    return x, r, c


def extract_patches(x: jnp.ndarray, tile: Tuple[int, int], halo: int) -> jnp.ndarray:
    """x: (H + 2·halo, W + 2·halo, ...) pre-padded → patches
    (nt_r, nt_c, tile+2·halo, tile+2·halo, ...); H, W must divide by tile."""
    th, tw = tile
    H = x.shape[0] - 2 * halo
    W = x.shape[1] - 2 * halo
    assert H % th == 0 and W % tw == 0, (x.shape, tile, halo)
    rows = _strided_windows(x, 0, H // th, th, halo)  # (nt_r, th+2h, W+2h, ...)
    both = _strided_windows(rows, 2, W // tw, tw, halo)  # (nt_r, th+2h, nt_c, tw+2h, ...)
    return jnp.moveaxis(both, 2, 1)


def _strided_windows(x: jnp.ndarray, axis: int, n: int, step: int, halo: int):
    """The ``n`` overlapping windows ``[i·step, i·step + step + 2·halo)`` of
    ``x`` along ``axis``, as a new window axis at ``axis`` followed by the
    window extent.  Built from a few shifted block reshapes — a constant-size
    trace whatever ``n`` is."""
    size = step + 2 * halo
    k = -(-size // step)  # blocks one window spans
    # room for the k shifted block views; pad rows only feed discarded tails
    pad = (n + k - 1) * step - x.shape[axis]
    if pad > 0:
        widths = [(0, 0)] * x.ndim
        widths[axis] = (0, pad)
        x = jnp.pad(x, widths)
    shape = x.shape[:axis] + (n, step) + x.shape[axis + 1:]
    blocks = [
        jax.lax.slice_in_dim(x, m * step, (n + m) * step, axis=axis).reshape(shape)
        for m in range(k)
    ]
    win = jnp.concatenate(blocks, axis=axis + 1)
    return jax.lax.slice_in_dim(win, 0, size, axis=axis + 1)


def stitch_planar(p: jnp.ndarray, nt_r: int, nt_c: int, out_rows: int,
                  out_cols: int) -> jnp.ndarray:
    """Band-planar kernel tiles (nt_r·nt_c, B, th, tw) → (rows, cols, B),
    cropped.  Kernels write one lane-dense (th, tw) plane per band; the
    band-last image is assembled here, outside the kernel."""
    B, th, tw = p.shape[1:]
    y = p.reshape(nt_r, nt_c, B, th, tw).transpose(0, 3, 1, 4, 2)
    return y.reshape(nt_r * th, nt_c * tw, B)[:out_rows, :out_cols]
