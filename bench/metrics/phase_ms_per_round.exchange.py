"""Exchange: device time of the ops under the system's phase scope
``repro/phase/exchange/`` on the first chip (each round's halo exchange
of the boundary payload rows, the concatenation of the payload buffer it
lands in, and the warm start's exchanges once a call), per round, in
ms."""

from bench import spans


def read(ctx):
    return spans.phase_ms_per_round(ctx, "exchange")
