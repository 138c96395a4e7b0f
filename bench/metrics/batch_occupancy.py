"""Scheduler: mean of the engine counter ``active_per_step`` over ``max_slots``, %."""


def read(ctx):
    if not ctx.active_per_step:
        return None
    return 100.0 * sum(ctx.active_per_step) / len(ctx.active_per_step) / ctx.slots
