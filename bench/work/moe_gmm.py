"""Least work of one grouped expert GEMM call (``kernels/moe_gmm.py``).

Counts routed rows only, and the packed weights (codes with their
float32 scale and zero per group of ``group`` rows) of the experts that
got at least one row. Activations are bf16. A traced window's calls are
rebuilt from the engine's ``slot_counts``: per program step and layer,
the routed rows of each expert slot, one fused gate/up call and one down
call per bucket of bit width.
"""
import re

PATTERN = re.compile(r"^moe_gmm")


def packed_bytes(k: int, n: int, bits: int, group: int) -> float:
    """Codes of a ``[k, n]`` matrix at ``bits`` plus f32 scale and zero."""
    return k * n * bits / 8 + 2 * (-(-k // group)) * n * 4


def swiglu(rows: int, experts: int, d: int, f: int, bits: int, group: int):
    """Fused gate/up call: ``[rows, d] -> [rows, f]``; (flops, bytes)."""
    flops = 2 * 2 * rows * d * f
    nbytes = experts * 2 * packed_bytes(d, f, bits, group) + 2 * rows * (d + f)
    return flops, nbytes


def down(rows: int, experts: int, d: int, f: int, bits: int, group: int):
    """Down call: ``[rows, f] -> [rows, d]``; (flops, bytes)."""
    flops = 2 * rows * f * d
    nbytes = experts * packed_bytes(f, d, bits, group) + 2 * rows * (d + f)
    return flops, nbytes


def calls(ctx):
    d, f = ctx.model["d_model"], ctx.model["d_ff_expert"]
    for counts in ctx.step_counts:  # [L, slots]
        for layer in counts:
            for bits, start, count in ctx.buckets:
                c = layer[start:start + count]
                rows, touched = int(c.sum()), int((c > 0).sum())
                if rows:
                    yield swiglu(rows, touched, d, f, bits, ctx.group)
                    yield down(rows, touched, d, f, bits, ctx.group)
