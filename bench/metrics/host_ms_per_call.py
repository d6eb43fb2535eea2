"""Host: wall time of each traced call (the benchmark's
``bench/call`` span) less the interval in which that call's scan
executable ran on the device; the mean over the calls, in ms."""


def read(ctx):
    per_call = []
    for call in ctx.trace.calls:
        scans = [s for s in ctx.trace.scans
                 if call.start <= s.start <= call.end]
        if len(scans) == 1:
            s = scans[0]
            per_call.append((call.end - call.start) - (s.end - s.start))
    if not per_call:
        return None
    return sum(per_call) / len(per_call) / 1e3
