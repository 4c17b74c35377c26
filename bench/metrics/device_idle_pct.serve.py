"""Share of the traced serving window with no operation on the
device, %."""


def read(ctx):
    t = ctx.trace
    return 100.0 * t.idle_share if t.window_s and t.n_devices else None
