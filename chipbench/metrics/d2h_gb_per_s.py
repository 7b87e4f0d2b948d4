"""Device-to-host rate of the strip outputs: the ``bytes`` of the
``repro.d2h`` spans that start in the window over their summed durations,
in GB/s (1e9 bytes)."""
from progtrace import rate


def read(ctx):
    return rate(ctx, ("d2h",), 1e9)
