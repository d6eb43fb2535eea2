"""Host: bytes copied between host and device per scenario call, in MB
(10^6 bytes): the ``bytes`` args of the system's ``repro/host/prepare``
(host to device) and ``repro/host/fetch`` (device to host) spans in the
window, over the calls.  ``up_mb`` and ``down_mb`` give each direction."""

from bench import spans


def read(ctx):
    parsed = spans.of(ctx)
    if parsed is None:
        return None
    calls = len(spans.scenarios(parsed))
    if not calls or not spans.steps(parsed, "prepare"):
        return None
    up = spans.step_bytes(parsed, "prepare") / calls / 1e6
    down = spans.step_bytes(parsed, "fetch") / calls / 1e6
    return dict(value=up + down, up_mb=up, down_mb=down)
