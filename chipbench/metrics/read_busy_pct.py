"""Share of the window covered by the benchmark's spans around the stored
scene's ``TiledSource.generate`` calls (union, host clock)."""


def read(ctx):
    return ctx.span_share("read")
