"""Whole step's share of the bf16 peak, %: model FLOPs of every token the traced window processed (prompt and output) over its host-clock seconds."""
from bench import roofline


def read(ctx):
    if ctx.span_s <= 0 or not ctx.tokens_processed:
        return None
    flops = ctx.tokens_processed * roofline.model_flops_per_token(
        ctx.model, roofline.mean_context(ctx))
    return 100.0 * flops / ctx.span_s / ctx.peak["bf16_flops"]
