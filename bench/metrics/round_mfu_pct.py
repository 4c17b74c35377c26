"""The whole round's share of the chip's roofline: the least time of a
round from the peaks over its measured wall time, %."""


def read(ctx):
    if not ctx.units or not ctx.unit_s:
        return None
    least = ctx.work["round"].least_seconds(ctx.device_kind)
    return 100.0 * least / ctx.unit_s
