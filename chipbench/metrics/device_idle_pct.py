"""Share of the window in which no operation ran on the device (trace)."""


def read(ctx):
    lo, hi = ctx.window
    return 100.0 * (1.0 - ctx.trace.busy_s(ctx.window) / (hi - lo))
