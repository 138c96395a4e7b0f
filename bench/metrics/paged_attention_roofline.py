"""Paged decode attention (``kernels/paged_attention.py``): least time over device time, %; KV of each sequence's actual length."""
from bench import roofline


def read(ctx):
    return roofline.share("paged_attention", ctx)
