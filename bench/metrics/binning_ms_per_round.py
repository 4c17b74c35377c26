"""Binning device time per round (scope ``repro.bin_features``), ms."""


def read(ctx):
    s = ctx.trace.scope_seconds("repro.bin_features")
    return 1e3 * s / ctx.units if s and ctx.units else None
