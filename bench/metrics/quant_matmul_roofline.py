"""4-bit dequant-matmul (``kernels/quant_matmul.py``) of attention and shared experts: least time over device time, %."""
from bench import roofline


def read(ctx):
    return roofline.share("quant_matmul", ctx)
