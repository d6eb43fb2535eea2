"""Algorithmic work of one CL-ADMM round (paper §4.2, Eq. 7), quadratic
loss, exact primal, float32 state.

Counted from the algorithm, not from a compiled program.  Per round, with
D the payloads delivered in it (the call's ``delivered`` counter over its
rounds); each delivery makes its receiver run the primal and update its
side of the edge:

* primal: read the four Z and L rows (4 k_max p), the weights (k_max),
  degree, count and sum of samples (p + 2); write the model (p) and the
  row of copies (k_max p).  9 k_max p + 3 p FLOPs (the slot terms, their
  sums and the copies' solve);
* edge: read the partner's payload (model, copy, two duals: 4 p) and its
  own model, copy and duals (4 p); write Zo, Zn, Lo, Ln (4 p).  14 p FLOPs;
* events: as for MP (3 n + 8 B words);
* per call, spread over its rounds: the warm start (read n p, write the
  model and five (n, k_max, p) slot arrays) and the snapshots.
"""

WORD = 4  # bytes of a float32 / int32


def per_round(n: int, batch: int, k_max: int, p: int, delivered: int,
              rounds: int, record_every: int) -> tuple:
    """(FLOPs, bytes) of one round, averaged over a call of ``rounds``."""
    d = delivered / rounds
    words = d * (4 * k_max * p + k_max + p + 2 + p + k_max * p)
    flops = d * (9 * k_max * p + 3 * p)
    words += d * 12 * p
    flops += d * 14 * p
    words += 3 * n + 8 * batch
    per_call = n * p + n * p + 5 * n * k_max * p \
        + (rounds // record_every) * n * p
    words += per_call / rounds
    return flops, words * WORD
