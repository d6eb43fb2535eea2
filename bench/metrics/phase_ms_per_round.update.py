"""Scan: device time of the ops under the system's phase scope
``repro/phase/update/`` (the MP round's Eq. 6 update and its theta
scatter), per round, in ms."""

from bench import spans


def read(ctx):
    return spans.phase_ms_per_round(ctx, "update")
