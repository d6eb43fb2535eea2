"""Scan: device time of the scan executable's runs over the rounds they
ran, in ms."""


def read(ctx):
    if not ctx.trace.scans:
        return None
    return ctx.scan_s_per_round() * 1e3
