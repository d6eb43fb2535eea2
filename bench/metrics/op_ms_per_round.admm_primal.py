"""Ops: device time of the ops under the dispatch scope
``repro/admm_primal/`` (kernels/dispatch), per round, in ms."""

SCOPE = "repro/admm_primal/"


def read(ctx):
    us = ctx.trace.scope_us(SCOPE)
    if us <= 0 or ctx.rounds == 0:
        return None
    return us / 1e3 / ctx.rounds
