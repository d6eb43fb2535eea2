"""Scan: device time of the ops under the system's phase scope
``repro/phase/draw/`` (the event draw: the wake-up, fault and churn
draws and the event counters inside the scan (MP), or the whole
``_draw_stream`` executable run before the scan (CL)), per round, in ms."""

from bench import spans


def read(ctx):
    return spans.phase_ms_per_round(ctx, "draw")
