"""Traversal device time per request (scope ``repro.traverse``), ms."""


def read(ctx):
    s = ctx.trace.scope_seconds("repro.traverse")
    return 1e3 * s / ctx.units if s and ctx.units else None
