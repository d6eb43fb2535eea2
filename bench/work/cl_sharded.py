"""Algorithmic work of one chip's share of a row-sharded CL-ADMM round
(paper §4.2, Eq. 7), quadratic loss, exact primal, float32 state.

The users, and so the deliveries, spread evenly over ``SHARDS`` chips, so
a chip does the round's work of ``bench/work/cl.py`` over ``SHARDS``.  On
top, its halo traffic: it publishes its ``BOUNDARY_ROWS`` boundary users
and reads its ``HALO_ROWS`` halo users, each a payload of
(1 + 3 k_max) p words (the model, the k_max copies and the two k_max
duals).  The two counts are the largest over the shards of the greedy
4-way partition (seed 0) of the configuration's graph
(``cl-knn200k-p100-4chip``: n = 200,000, k_max = 17), as
``repro.simulate.GraphPartition`` lays it out; a chip's share is the
larger one's, as the slowest chip sets the round.
"""

from bench.work import cl

SHARDS = 4
BOUNDARY_ROWS = 1354
HALO_ROWS = 1337
WORD = cl.WORD


def per_round(n: int, batch: int, k_max: int, p: int, delivered: int,
              rounds: int, record_every: int) -> tuple:
    """(FLOPs, bytes) of one chip's share of a round, averaged over a call
    of ``rounds``."""
    flops, nbytes = cl.per_round(n, batch, k_max, p, delivered, rounds,
                                 record_every)
    halo = (BOUNDARY_ROWS + HALO_ROWS) * (1 + 3 * k_max) * p
    return flops / SHARDS, nbytes / SHARDS + halo * WORD
