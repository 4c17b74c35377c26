"""Histogram device time per round (scope ``repro.hist_levels``), ms."""


def read(ctx):
    s = ctx.trace.scope_seconds("repro.hist_levels")
    return 1e3 * s / ctx.units if s and ctx.units else None
