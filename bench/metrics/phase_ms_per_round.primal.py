"""Scan: device time of the ops under the system's phase scope
``repro/phase/primal/`` (the CL round's primal solve and its theta/K row
scatters), per round, in ms."""

from bench import spans


def read(ctx):
    return spans.phase_ms_per_round(ctx, "primal")
