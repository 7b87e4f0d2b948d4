"""Shared parts of the output checks: which rows are compared, and how.

Each check compares windows of ``window_rows`` full-width product rows, read
back from the product file, with the plain reference of the pipeline
(``pipelines/<name>.py``).  The windows are drawn from the seed: the first
and the last rows of the image, one window across each seam between chips of
a tile grid, and ``random_windows`` more at random row offsets (not aligned
to the strips, so strip seams fall inside them).
"""
from __future__ import annotations

from typing import List, Sequence

import numpy as np


def sample_windows(seed: int, rows: int, window_rows: int, seams: Sequence[int],
                   k: int) -> List[int]:
    """Start rows of the compared windows, sorted and distinct."""
    w = min(window_rows, rows)
    starts = {0, rows - w}
    for s in seams:
        starts.add(min(max(0, s - w // 2), rows - w))
    rng = np.random.default_rng([int(seed) & 0xFFFFFFFFFFFFFFFF, 1])
    for _ in range(k):
        starts.add(int(rng.integers(0, rows - w + 1)))
    return sorted(starts)


def rounder(dtype_name: str):
    """A function that rounds float32 values to the precision of
    ``dtype_name``: the identity for float32, and for bfloat16
    ``lax.reduce_precision`` to 8 exponent and 7 mantissa bits.  The
    references compute in float32 and round after every operation, because
    XLA may keep bfloat16 intermediates in float32 (excess precision), which
    would let the control pass for the wrong reason."""
    from jax import lax

    if dtype_name == "float32":
        return lambda v: v
    if dtype_name == "bfloat16":
        return lambda v: lax.reduce_precision(v, exponent_bits=8, mantissa_bits=7)
    raise ValueError(f"no rounding to {dtype_name}")


def rel_gap(got: np.ndarray, want: np.ndarray) -> float:
    """Widest gap ``|got - want| / (1 + |want|)``; non-finite reads as inf."""
    got = got.astype(np.float64)
    want = want.astype(np.float64)
    if not np.isfinite(got).all():
        return float("inf")
    return float(np.max(np.abs(got - want) / (1.0 + np.abs(want))))


def mismatches(got: np.ndarray, want: np.ndarray) -> float:
    """Number of values that differ."""
    return float(np.count_nonzero(got != want))
