"""Grouped expert GEMM (``kernels/moe_gmm.py``): its calls' least time over its device time, %."""
from bench import roofline


def read(ctx):
    return roofline.share("moe_gmm", ctx)
