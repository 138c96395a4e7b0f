"""Least work of one dequant-matmul call (``kernels/quant_matmul.py``).

``[rows, k] @ dequant([k, n])`` with codes of ``bits`` and f32 scale and
zero per group of ``group`` rows; activations bf16. A traced window's
calls are, per program step and layer, one per attention and shared-expert
projection over the step's tokens (its routed rows over top-k).
"""
import re

from bench import roofline

PATTERN = re.compile(r"^quant_matmul")


def call(rows: int, k: int, n: int, bits: int, group: int):
    flops = 2 * rows * k * n
    nbytes = k * n * bits / 8 + 2 * (-(-k // group)) * n * 4 + 2 * rows * (k + n)
    return flops, nbytes


def calls(ctx):
    m = ctx.model
    for counts in ctx.step_counts:
        for layer in counts:
            rows = int(layer.sum()) // m["top_k"]
            if rows:
                for k, n in roofline.projections(m):
                    yield call(rows, k, n, ctx.attn_bits, ctx.group)
