"""Split-gain device time per round (scope ``repro.split_gain``), ms."""


def read(ctx):
    s = ctx.trace.scope_seconds("repro.split_gain")
    return 1e3 * s / ctx.units if s and ctx.units else None
