"""Share of the window in which the device idled while the dispatching
thread pulled a finished strip to the host (``repro.d2h``), program spans
on the device trace."""
from progtrace import idle_pct


def read(ctx):
    return idle_pct(ctx, ("d2h",))
