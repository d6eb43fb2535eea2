"""Host: self time of the system's ``repro/host/prepare`` spans (table
upload, host inputs converted and uploaded, key splits, CL's event
stream dispatched), mean per scenario call in the window, in ms."""

from bench import spans


def read(ctx):
    parsed = spans.of(ctx)
    return None if parsed is None else spans.per_call(parsed, "prepare")
