"""Share of the window in which the device idled while the dispatching
thread was in ``repro.dispatch``: enqueueing a strip's program and waiting
for the device to run it, so the program's launch and completion latency
(program spans on the device trace)."""
from progtrace import idle_pct


def read(ctx):
    return idle_pct(ctx, ("dispatch",))
