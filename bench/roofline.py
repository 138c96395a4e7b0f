"""Kernels' shares of their rooflines, and the whole step's model FLOPs.

Each call's bound is ``max(flops / peak, bytes / HBM bandwidth)`` with the
peaks of ``bench/peaks.json``; a kernel's roofline share is the sum of its
calls' bounds over the kernel's device seconds in the trace. The calls
come from the kernel's module in ``bench/work``, found by name.
"""
from __future__ import annotations

import numpy as np

__all__ = ["bound", "least_time", "share", "projections",
           "model_flops_per_token", "mean_context"]


def bound(flops: float, nbytes: float, peak: dict):
    """(seconds, which) for one call."""
    t_c, t_m = flops / peak["bf16_flops"], nbytes / peak["hbm_bytes_per_s"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")


def least_time(calls, peak: dict) -> dict:
    """Summed bounds of ``calls`` (``(flops, bytes)`` pairs), in all and
    split by the bound that applies."""
    acc = {"s": 0.0, "compute": 0.0, "memory": 0.0}
    for flops, nbytes in calls:
        t, which = bound(flops, nbytes, peak)
        acc["s"] += t
        acc[which] += t
    return acc


def share(kernel: str, ctx):
    """Percent of ``kernel``'s device time that its calls' least time
    fills; ``None`` where the trace holds no such kernel or no call."""
    from bench import work

    t = ctx.trace["kernel_s"].get(kernel, 0.0)
    if t <= 0:
        return None
    acc = least_time(work.kernel(kernel).calls(ctx), ctx.peak)
    if acc["s"] <= 0:
        return None
    return 100.0 * acc["s"] / t


def projections(m: dict):
    """``(k, n)`` of each attention and shared-expert projection."""
    d, q, kv = m["d_model"], m["num_heads"] * m["head_dim"], m["num_kv_heads"] * m["head_dim"]
    out = [(d, q), (d, kv), (d, kv), (q, d)]
    fs = m["d_ff_expert"] * m["num_shared_experts"]
    if fs:
        out += [(d, fs), (d, fs), (fs, d)]
    return out


def model_flops_per_token(m: dict, context: float) -> float:
    """Forward FLOPs of one token at a KV ``context``: projections,
    attention over the context, router, top-k routed and shared experts,
    output head."""
    d, f = m["d_model"], m["d_ff_expert"]
    per_layer = sum(2 * k * n for k, n in projections(m))
    per_layer += 4 * context * m["num_heads"] * m["head_dim"]
    per_layer += 2 * d * m["num_experts"]
    per_layer += m["top_k"] * 3 * 2 * d * f
    return m["num_layers"] * per_layer + 2 * d * m["vocab_size"]


def mean_context(ctx) -> float:
    lengths = [x for step in ctx.decode_lengths for x in step]
    return float(np.mean(lengths)) if lengths else 0.0
