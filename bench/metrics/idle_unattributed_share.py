"""Device: the share of the window's device-idle time (first chip) in
which none of the system's ``repro/host/<step>`` spans was open, in %.
Extra fields: the idle time per scenario call under each step's spans
(``idle_ms_per_call_<step>``) and under none of them
(``idle_ms_per_call_unattributed``), in ms."""

from bench import spans


def read(ctx):
    parsed = spans.of(ctx)
    if parsed is None:
        return None
    calls = len(spans.scenarios(parsed))
    host = [s for s in spans.in_window(parsed.host, parsed.window)
            if s.name.startswith(spans.HOST)]
    if not calls or not host:
        return None
    idle = spans.idle(ctx)
    total = sum(s.end - s.start for s in idle)

    def under(found):
        return spans.overlap_us(
            idle, [spans.trace_mod.Span(s.start, s.end) for s in found])

    unattributed = total - under(host)
    out = dict(value=100.0 * unattributed / total if total > 0 else 0.0)
    for step in sorted({s.name[len(spans.HOST):] for s in host}):
        out[f"idle_ms_per_call_{step}"] = \
            under([s for s in host if s.name == spans.HOST + step]) \
            / calls / 1e3
    out["idle_ms_per_call_unattributed"] = unattributed / calls / 1e3
    return out
