"""P3, pansharpening: bicubic resample of XS onto the PAN grid, then RCS
fusion ``out_b = XS_up_b * PAN / box(PAN)``.

Work per output (PAN-grid) pixel, from logical shapes and stored dtypes:

- bytes: PAN read once (one band at its itemsize), XS read once (its bands
  at their itemsize, over ratio^2 output pixels each), and the float32
  product written once: ``pan + xs_bands * xs / ratio^2 + 4 * bands``
  (18.5 B for uint16 at ratio 4).
- operations: the separable bicubic (4 taps: 7 operations a sample) along
  rows at XS width and along columns at PAN width, per band,
  ``7 * bands * (1 / ratio + 1)``; the (2r+1)^2 box sum and its division
  (``(2r+1)^2``); the ratio (2); one product per band.

Kernel ``pansharpen_rcs`` runs the fusion only.  Its inputs are the
resampled XS (float32) and the PAN at its stored dtype; it writes the
float32 product: ``4 * bands + pan + 4 * bands`` bytes and
``(2r+1)^2 + 2 + bands`` operations.

The reference computes Keys bicubic weights (a = -0.5) in float64 on the
host from pixel-centre alignment with edge-clamped taps, resamples rows then
columns, sums the PAN box in shift order, and fuses.  It imports nothing of
the program.
"""
from __future__ import annotations

import functools

import numpy as np

RASTERS = ("xs", "pan")
CHECK = "fusion_gap"


def build(sources, params, mapper_factory):
    from repro import pipelines as PP

    if params["radius"] != params["ratio"] // 2 or params["method"] != "bicubic":
        raise ValueError("the P3 builder fuses bicubic XS with a ratio/2 box")
    return PP.p3_pansharpening(
        sources["xs"], sources["pan"], ratio=params["ratio"],
        mapper_factory=mapper_factory,
    )


def _sizes(pair):
    p, m = pair
    xs, pan = p.sources()
    xi, pi, oi = p.info(xs), p.info(pan), p.info(m)
    return (np.dtype(xi.dtype).itemsize, xi.bands, np.dtype(pi.dtype).itemsize,
            oi.bands, np.dtype(oi.dtype).itemsize)


def work(pair, params):
    xs_b, xs_n, pan_b, out_n, out_b = _sizes(pair)
    ratio, r = params["ratio"], params["radius"]
    ops = 7 * xs_n * (1 / ratio + 1) + (2 * r + 1) ** 2 + 2 + out_n
    return float(ops), float(pan_b + xs_n * xs_b / ratio ** 2 + out_n * out_b)


def fusion_work(pair, params):
    xs_b, xs_n, pan_b, out_n, out_b = _sizes(pair)
    r = params["radius"]
    return float((2 * r + 1) ** 2 + 2 + out_n), float(4 * xs_n + pan_b + out_n * out_b)


KERNELS = {"pansharpen_rcs": fusion_work}


def keys_taps(n_out: int, n_in: int, ratio: int):
    """Tap indices (n_out, 4) and Keys cubic weights (n_out, 4) of the
    output samples of one axis, pixel centres aligned, taps edge-clamped."""
    pos = (np.arange(n_out, dtype=np.float64) + 0.5) / ratio - 0.5
    base = np.floor(pos)
    t = pos - base
    d = np.abs(np.stack([t + 1.0, t, 1.0 - t, 2.0 - t], -1))
    a = -0.5
    w = np.where(
        d <= 1.0, (a + 2) * d**3 - (a + 3) * d**2 + 1,
        np.where(d < 2.0, a * d**3 - 5 * a * d**2 + 8 * a * d - 4 * a, 0.0),
    )
    idx = np.clip(base[:, None].astype(np.int64) + np.arange(-1, 3), 0, n_in - 1)
    return idx.astype(np.int32), w.astype(np.float32)


@functools.lru_cache(maxsize=None)
def _fuse_fn(radius, dtype_name):
    import jax
    import jax.numpy as jnp

    from check import rounder

    rd = rounder(dtype_name)
    k = 2 * radius + 1

    def fn(xs, ridx, rw, cidx, cw, pan):
        x = rd(xs.astype(jnp.float32))
        rw, cw = rd(rw), rd(cw)
        y = rd(x[ridx[:, 0]] * rw[:, 0, None, None])
        for t in range(1, 4):
            y = rd(y + rd(x[ridx[:, t]] * rw[:, t, None, None]))
        z = rd(y[:, cidx[:, 0]] * cw[None, :, 0, None])
        for t in range(1, 4):
            z = rd(z + rd(y[:, cidx[:, t]] * cw[None, :, t, None]))
        p = rd(pan.astype(jnp.float32))
        h, w = z.shape[:2]
        acc = jnp.zeros((h, w), jnp.float32)
        for u in range(k):
            for v in range(k):
                acc = rd(acc + p[u:u + h, v:v + w])
        smooth = rd(acc / (k * k))
        ratio = rd(p[radius:radius + h, radius:radius + w] / jnp.maximum(smooth, 1e-6))
        return rd(z * ratio[..., None])

    return jax.jit(fn)


def reference(gens, r0, r1, params, dtype="float32"):
    """Pansharpened rows [r0, r1) of the PAN grid, full width, float32;
    every operation rounded to ``dtype`` (the control: ``"bfloat16"``)."""
    ratio, radius = params["ratio"], params["radius"]
    xs_gen, pan_gen = gens["xs"], gens["pan"]
    xs = xs_gen.rows(0, xs_gen.spec["rows"])
    pan_rows, pan_cols = pan_gen.spec["rows"], pan_gen.spec["cols"]
    ridx, rw = keys_taps(pan_rows, xs.shape[0], ratio)
    cidx, cw = keys_taps(pan_cols, xs.shape[1], ratio)
    pan = pan_gen.rows_edge(r0 - radius, r1 + radius, pad_cols=radius)[..., 0]
    fn = _fuse_fn(radius, dtype)
    return np.asarray(fn(xs, ridx[r0:r1], rw[r0:r1], cidx, cw, pan))


def compare(got, want):
    from check import rel_gap

    return rel_gap(got, want)
