"""Device time per round in which no operation under a ``repro.``
scope ran, ms: the part of a round no layer accounts for."""


def read(ctx):
    t = ctx.trace
    if not t.n_devices or not ctx.units:
        return None
    return 1e3 * max(t.busy_s - t.scope_seconds("repro."), 0.0) / ctx.units
