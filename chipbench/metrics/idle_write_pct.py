"""Share of the window in which the device idled while the dispatching
thread was in the write stage: ``repro.begin``, ``repro.wait_write`` (the
write-behind queue full, or its drain at the pass's end) and ``repro.end``
(the writer's flush), program spans on the device trace."""
from progtrace import idle_pct


def read(ctx):
    return idle_pct(ctx, ("begin", "wait_write", "end"))
