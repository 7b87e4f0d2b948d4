"""Share of the window in which the device idled while the dispatching
thread waited for a strip's inputs (``repro.wait_inputs``: describe, plan
lookup and source read on the prefetch threads), program spans on the
device trace."""
from progtrace import idle_pct


def read(ctx):
    return idle_pct(ctx, ("wait_inputs",))
