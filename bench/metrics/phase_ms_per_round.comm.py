"""Scan: device time of the ops under the system's phase scope
``repro/phase/comm/`` (the MP round's message gathers and slot
scatters), per round, in ms."""

from bench import spans


def read(ctx):
    return spans.phase_ms_per_round(ctx, "comm")
