"""Rate of the strips' source reads: the ``bytes`` of the ``repro.read``
spans (``StreamingExecutor._prepare``) that start in the window over their
summed durations, in MB/s (1e6 bytes).  Nothing when the window holds no
such span, as in a program that does not emit it."""
from devtrace import MissingEvents
from progtrace import program_trace, rate


def read(ctx):
    if program_trace(ctx) is None:
        return None
    try:
        return rate(ctx, ("read",), 1e6)
    except MissingEvents:  # spans, but no ``read`` among them
        return None
