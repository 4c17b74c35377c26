"""Traversal's share of its roofline: least time / device time, %."""


def read(ctx):
    s = ctx.trace.scope_seconds("repro.traverse")
    if not s or not ctx.units:
        return None
    least = ctx.work["traversal"].least_seconds(ctx.device_kind)
    return 100.0 * least / (s / ctx.units)
