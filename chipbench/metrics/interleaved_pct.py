"""Share of the product's bytes that the writer had to interleave on the
host: Σ ``interleaved`` over Σ ``bytes`` of the ``repro.consume`` spans
(``TileWriter``) that start in the window, in %.  0 when every strip came
with C-ordered rows in the file's dtype.  Nothing when the window holds no
such span, or its spans carry no ``interleaved`` stat, as in a program from
before it."""
from devtrace import MissingEvents
from progtrace import program_trace


def read(ctx):
    tr = program_trace(ctx)
    if tr is None:
        return None
    try:
        spans = tr.program_spans(("consume",), ctx.window)
    except MissingEvents:
        return None
    if any("interleaved" not in st for _, _, _, st in spans):
        return None
    return 100.0 * sum(st["interleaved"] for *_, st in spans) / sum(
        st["bytes"] for *_, st in spans)
