"""The whole step's share of the chip's roofline: the least time the
pipeline's needed work (per output pixel, ``pipelines/<P>.py::work``) takes
at one chip's peaks, for the pixels committed in the window, over the
device's busy time in the window summed over chips (trace)."""


def read(ctx):
    busy = ctx.trace.busy_s(ctx.window) * ctx.n_chips
    return 100.0 * ctx.least_seconds(ctx.step_work) / busy if busy > 0 else None
