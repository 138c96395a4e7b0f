"""Model step: device seconds of the decode-megastep programs over the logical decode steps they ran, ms."""


def read(ctx):
    t = ctx.trace["program_s"].get("decode", 0.0)
    if t <= 0 or not ctx.decode_steps:
        return None
    return 1000.0 * t / ctx.decode_steps
