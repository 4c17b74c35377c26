"""Histogram's share of its roofline: least time / device time, %."""


def read(ctx):
    s = ctx.trace.scope_seconds("repro.hist_levels")
    if not s or not ctx.units:
        return None
    least = ctx.work["histogram"].least_seconds(ctx.device_kind)
    return 100.0 * least / (s / ctx.units)
