"""Least work of one paged decode attention call (``kernels/paged_attention.py``).

One query token per sequence; keys and values of each sequence's actual
length (not of the pool or the block table), bf16. A traced window's
calls are one per layer and decode step, over the KV lengths of the
sequences active in that step.
"""
import re

PATTERN = re.compile(r"^paged_attention")


def call(lengths, num_heads: int, num_kv_heads: int, head_dim: int):
    """(flops, bytes) for sequences of KV ``lengths``."""
    total = sum(lengths)
    flops = 4 * total * num_heads * head_dim  # q.k and p.v
    nbytes = 2 * 2 * total * num_kv_heads * head_dim  # K and V, bf16
    nbytes += 2 * 2 * len(lengths) * num_heads * head_dim  # q and out
    return flops, nbytes


def calls(ctx):
    m = ctx.model
    for lengths in ctx.decode_lengths:
        if lengths:
            one = call(lengths, m["num_heads"], m["num_kv_heads"], m["head_dim"])
            for _ in range(m["num_layers"]):
                yield one
