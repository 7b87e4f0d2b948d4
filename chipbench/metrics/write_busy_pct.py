"""Share of the window covered by the benchmark's spans around the product
sink's ``TileWriter.consume`` and ``end`` calls (union, host clock)."""


def read(ctx):
    return ctx.span_share("write")
