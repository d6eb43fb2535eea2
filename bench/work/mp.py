"""Algorithmic work of one MP round (paper §3.2, Eq. 6), float32 state.

Counted from the algorithm, not from a compiled program: the same numbers
whatever implements the round.  Per round, with D the messages delivered
in it (the call's ``delivered`` counter over its rounds):

* communication: each delivered message reads the sender's model and
  writes it into the receiver's copy: 2 p words;
* update: each receiving endpoint reads its row of k_max copies and
  weights, its solitary model and confidence, and writes its model:
  (k_max p + k_max + 2 p + 1) words and 2 k_max p + 5 p FLOPs
  (a multiply-add per copy element, the Eq. 6 blend and divide);
* events: the wake-up cdf over the n rates (read and write n words), the
  per-agent lag draw (n words) and B wake-ups of 8 words each;
* per call, spread over its rounds: the warm start (read n p, write
  n k_max p copies and n p models) and the snapshots (n_rec n p words).
"""

WORD = 4  # bytes of a float32 / int32


def per_round(n: int, batch: int, k_max: int, p: int, delivered: int,
              rounds: int, record_every: int) -> tuple:
    """(FLOPs, bytes) of one round, averaged over a call of ``rounds``."""
    d = delivered / rounds
    words = d * 2 * p
    words += d * (k_max * p + k_max + 2 * p + 1)
    flops = d * (2 * k_max * p + 5 * p)
    words += 3 * n + 8 * batch
    per_call = n * p + n * k_max * p + n * p + (rounds // record_every) * n * p
    words += per_call / rounds
    return flops, words * WORD
