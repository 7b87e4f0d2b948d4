"""Mean duration of the describe pass of one strip: the ``repro.describe``
spans (prefetch threads) that start in the window, in microseconds."""
from progtrace import program_trace


def read(ctx):
    tr = program_trace(ctx)
    if tr is None:
        return None
    spans = tr.program_spans(("describe",), ctx.window)
    return 1e6 * sum(b - a for _, a, b, _ in spans) / len(spans)
