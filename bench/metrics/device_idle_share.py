"""Share of the traced slice in which no operation ran on the device:
1 - (union of the device's op intervals) / slice, averaged over chips."""
NAME = "device_idle_share"
UNIT = "%"
LAYER = "device"
MOVES = "output_tokens_per_s"


def read(ctx):
    span = ctx.hi - ctx.lo
    if span <= 0 or not ctx.trace.ops:
        return None
    return 100.0 * (1.0 - ctx.trace.busy_ns(ctx.lo, ctx.hi) / span)
