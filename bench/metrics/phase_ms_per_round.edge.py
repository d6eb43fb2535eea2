"""Scan: device time of the ops under the system's phase scope
``repro/phase/edge/`` (the CL round's edge phase (``cl_edge_step`` and
its index concatenations)), per round, in ms."""

from bench import spans


def read(ctx):
    return spans.phase_ms_per_round(ctx, "edge")
