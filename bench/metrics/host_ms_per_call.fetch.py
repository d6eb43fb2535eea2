"""Host: self time of the system's ``repro/host/fetch`` spans (snapshots,
counters and CL's stream totals copied back to the host), mean per
scenario call in the window, in ms."""

from bench import spans


def read(ctx):
    parsed = spans.of(ctx)
    return None if parsed is None else spans.per_call(parsed, "fetch")
