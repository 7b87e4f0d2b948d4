"""Roofline share of the pansharpening kernel ``pansharpen_rcs`` (trace):
the least time of its work (``pipelines/P3.py::fusion_work``) over its
device time."""


def read(ctx):
    return ctx.kernel_roofline("pansharpen_rcs")
