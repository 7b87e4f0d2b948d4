"""Pansharpening (paper pipeline P3): fuse PAN + upsampled XS.

Ratio Component Substitution (the OTB BayesianFusion/RCS default):

    out_b = XS↑_b · PAN / smooth(PAN)

where smooth is a box filter whose support matches the XS→PAN resolution
ratio.  The full P3 graph is ``Resample(XS → PAN grid)`` + this fusion
filter; see ``repro.pipelines.pansharpening``.
"""
from __future__ import annotations

from typing import Optional

import jax.numpy as jnp
import numpy as np

from repro.core.process_object import Filter, ImageInfo
from repro.core.region import ImageRegion


def pansharpen_ref(xs_up: jnp.ndarray, pan: jnp.ndarray, radius: int) -> jnp.ndarray:
    """xs_up: (H, W, B); pan: (H + 2r, W + 2r, 1) pre-padded. → (H, W, B).

    The box sum adds the (2r+1)² shifted windows in order: exact for
    integer-valued PAN at any width, where a cumulative-sum filter's running
    totals outgrow float32's 24-bit significand on a wide strip."""
    k = 2 * radius + 1
    H, W = xs_up.shape[:2]
    pan = pan.astype(jnp.float32)
    acc = jnp.zeros((H, W) + pan.shape[2:], jnp.float32)
    for u in range(k):
        for v in range(k):
            acc = acc + pan[u : u + H, v : v + W]
    smooth = acc / (k * k)
    ratio = pan[radius : radius + H, radius : radius + W] / jnp.maximum(smooth, 1e-6)
    return xs_up.astype(jnp.float32) * ratio


class PansharpenFuse(Filter):
    """``use_pallas`` is tri-state (``kernels.ops.resolve_use_pallas``):
    True forces the Pallas kernel (interpret mode on CPU), False the jnp
    reference, None defers to ``REPRO_USE_PALLAS`` / the backend."""

    n_inputs = 2  # (xs_up, pan)
    cost_per_pixel = 6.0

    def __init__(self, radius: int = 2, use_pallas: Optional[bool] = None,
                 name=None):
        super().__init__(name)
        self.radius = radius
        self.use_pallas = use_pallas

    def output_info(self, xs_info: ImageInfo, pan_info: ImageInfo) -> ImageInfo:
        if (xs_info.rows, xs_info.cols) != (pan_info.rows, pan_info.cols):
            raise ValueError("xs_up and pan grids must match")
        return ImageInfo(xs_info.rows, xs_info.cols, xs_info.bands, np.float32, pan_info.geo)

    def requested_region(self, out_region: ImageRegion, xs_info, pan_info):
        return (out_region, out_region.pad(self.radius))

    def generate(self, out_region: ImageRegion, xs_up, pan) -> jnp.ndarray:
        from repro.kernels import ops  # deferred: kernels.ref imports filters

        return ops.pansharpen(xs_up, pan, self.radius, use_pallas=self.use_pallas)

    # -- plan-layer Pallas fast path -----------------------------------------
    def pallas_plan(self) -> bool:
        from repro.kernels import ops

        return ops.resolve_use_pallas(self.use_pallas)

    def pallas_body(self, pre_fns=(None, None)):
        from repro.kernels import pansharpen as psk

        pre_xs, pre_pan = pre_fns

        def body(xs_up, pan):
            return psk.pansharpen(
                xs_up, pan, self.radius, pre_xs=pre_xs, pre_pan=pre_pan
            )

        return body
