"""Plan compilations inside the window: the delta of the ``PlanCache``
``compiles`` counter.  It should read 0."""


def read(ctx):
    return float(ctx.compiles_in_window)
