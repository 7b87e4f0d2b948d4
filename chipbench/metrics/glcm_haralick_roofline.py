"""Roofline share of the GLCM kernel ``glcm_haralick`` (trace): the least
time of its work (``pipelines/P2.py::work``) over its device time."""


def read(ctx):
    return ctx.kernel_roofline("glcm_haralick")
