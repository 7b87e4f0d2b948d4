"""Mean-shift filtering (paper pipeline P5).

Mode-search smoothing as in OTB's MeanShiftSmoothing: each pixel's range
value v is iterated toward the weighted mean of its fixed spatial window,
weighted by a flat range kernel of bandwidth ``hr``:

    v ← Σ_w  x_w · 1[|x_w − v|² ≤ hr²]  /  Σ_w 1[...]

(``n_iter`` fixed iterations; flat kernels are OTB's default).  The spatial
window stays centered on the source pixel, so the halo is exactly ``hs`` and
the filter is region-independent — the paper's streamability condition.
The paper's Table 2 shows P5 with the *largest* run-time variance (±137 s at
N=1): its cost depends on image content, which is what motivated their
dynamic-load-balancing future work; our LPT scheduler targets exactly this.
"""
from __future__ import annotations

from typing import Optional

import jax.numpy as jnp
import numpy as np

from repro.core.process_object import Filter, ImageInfo
from repro.core.region import ImageRegion


def meanshift_ref(x: jnp.ndarray, hs: int, hr: float, n_iter: int) -> jnp.ndarray:
    """x: (H + 2hs, W + 2hs, B) pre-padded → (H, W, B).

    The window offsets accumulate in row-major order and the squared range
    distance sums in band order — the Pallas kernel's order.  A flat range
    kernel turns one rounding difference at its ``hr`` threshold into a
    whole neighbor gained or lost, so the oracle fixes the order instead of
    leaving it to a reduction."""
    H = x.shape[0] - 2 * hs
    W = x.shape[1] - 2 * hs
    x = x.astype(jnp.float32)
    bands = [x[..., b] for b in range(x.shape[-1])]
    v = [xb[hs : hs + H, hs : hs + W] for xb in bands]
    hr2 = hr * hr
    for _ in range(n_iter):
        num = [jnp.zeros((H, W), jnp.float32) for _ in bands]
        den = jnp.zeros((H, W), jnp.float32)
        for u in range(2 * hs + 1):
            for w in range(2 * hs + 1):
                xw = [xb[u : u + H, w : w + W] for xb in bands]
                d2 = (xw[0] - v[0]) ** 2
                for a, c in zip(xw[1:], v[1:]):
                    d2 = d2 + (a - c) ** 2
                m = (d2 <= hr2).astype(jnp.float32)
                num = [n + a * m for n, a in zip(num, xw)]
                den = den + m
        v = [n / jnp.maximum(den, 1e-12) for n in num]
    return jnp.stack(v, axis=-1)


class MeanShift(Filter):
    """``use_pallas`` is tri-state (``kernels.ops.resolve_use_pallas``):
    True forces the Pallas kernel (interpret mode on CPU), False the jnp
    reference, None defers to ``REPRO_USE_PALLAS`` / the backend."""

    cost_per_pixel = 40.0

    def __init__(self, hs: int = 3, hr: float = 100.0, n_iter: int = 4,
                 use_pallas: Optional[bool] = None, name=None):
        super().__init__(name)
        self.hs, self.hr, self.n_iter = hs, hr, n_iter
        self.use_pallas = use_pallas

    def output_info(self, info: ImageInfo) -> ImageInfo:
        return ImageInfo(info.rows, info.cols, info.bands, np.float32, info.geo)

    def requested_region(self, out_region: ImageRegion, info: ImageInfo):
        return (out_region.pad(self.hs),)

    def generate(self, out_region: ImageRegion, x: jnp.ndarray) -> jnp.ndarray:
        from repro.kernels import ops  # deferred: kernels.ref imports filters

        return ops.meanshift(
            x, self.hs, self.hr, self.n_iter, use_pallas=self.use_pallas
        )

    # -- plan-layer Pallas fast path -----------------------------------------
    def pallas_plan(self) -> bool:
        from repro.kernels import ops

        return ops.resolve_use_pallas(self.use_pallas)

    def pallas_body(self, pre_fns=(None,)):
        from repro.kernels import meanshift as msk

        def body(x):
            return msk.meanshift(
                x, self.hs, self.hr, self.n_iter, pre_fn=pre_fns[0]
            )

        return body
