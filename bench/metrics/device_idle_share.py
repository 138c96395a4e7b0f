"""Device: 1 - (union of device-op intervals) / traced window, %."""


def read(ctx):
    w = ctx.trace["window_s"]
    if w <= 0 or ctx.trace["busy_s"] <= 0:
        return None
    return 100.0 * (1.0 - ctx.trace["busy_s"] / w)
