"""The whole request's share of the chip's roofline: the least time of a
request from the peaks over the window's wall time per request, %."""


def read(ctx):
    if not ctx.units or not ctx.unit_s:
        return None
    least = ctx.work["request"].least_seconds(ctx.device_kind)
    return 100.0 * least / ctx.unit_s
