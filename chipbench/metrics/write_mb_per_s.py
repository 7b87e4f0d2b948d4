"""Rate of the product writer: the ``bytes`` of the ``repro.consume`` and
``repro.flush`` spans (``TileWriter``) that start in the window over their
summed durations, in MB/s (1e6 bytes)."""
from progtrace import rate


def read(ctx):
    return rate(ctx, ("consume", "flush"), 1e6)
