"""Device: 1 - (union of the device's op intervals / traced window), in %."""


def read(ctx):
    window = ctx.trace.window_s()
    if window <= 0 or not ctx.trace.ops:
        return None
    return 100.0 * (1.0 - ctx.trace.busy_s() / window)
