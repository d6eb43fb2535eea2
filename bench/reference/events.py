"""Plain reference of the scenario's event process (paper §3.2 / §4.2).

Imports nothing of the system under test.  It draws, from a call's seed,
the same random numbers in the same order that the scenario definition
prescribes, and turns them into events by the definition:

* per-agent base rates: 1, or ``straggler_factor`` for a Bernoulli
  ``straggler_frac`` share of agents (drawn once per call);
* one key per round, split into an event key and a churn key;
* B wake-ups categorical in ``rate * active`` (inverse cdf), a uniform
  neighbour slot in the waker's sorted neighbour list, independent
  per-direction drops, a cut across the two spatial halves inside the
  partition window, both directions lost if an endpoint is inactive, and
  a one-round-stale payload when the sender lags this round (one Bernoulli
  draw per agent per round);
* after the round, each agent toggles active with ``churn_rate``.

The graph is the benchmark's own CSR (``bench/graph.py``): slot ``s`` of
agent ``i`` is its ``s``-th smallest neighbour, and ``rev[e]`` is the slot
of ``i`` in the row of ``j`` for the edge ``e = (i, j)``.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np


class RefGraph(NamedTuple):
    """Device arrays of the CSR graph the references work on."""

    indptr: jnp.ndarray     # (n + 1,) int32
    indices: jnp.ndarray    # (2E,) int32
    rev: jnp.ndarray        # (2E,) int32 slot of the source in the target row
    deg: jnp.ndarray        # (n,) int32
    half: jnp.ndarray       # (n,) bool, the side a partition cuts between
    pad_idx: jnp.ndarray    # (n, k_max) int32 edge ids of each row, padded
    pad_live: jnp.ndarray   # (n, k_max) bool


def ref_graph(graph) -> RefGraph:
    """Device form of a ``bench.graph.Graph`` (host numpy work, O(E log E))."""
    indptr = np.asarray(graph.indptr, np.int64)
    indices = np.asarray(graph.indices, np.int64)
    n = indptr.shape[0] - 1
    deg = np.diff(indptr)
    src = np.repeat(np.arange(n), deg)
    # edge (j, i) for every edge (i, j): rows are sorted, so the position
    # of (j, i) in lexicographic order is its CSR id
    back = np.lexsort((src, indices))          # order of (indices, src)
    rev = np.empty_like(indices)
    rev[back] = np.arange(indices.shape[0]) - indptr[indices[back]]
    k_max = int(deg.max())
    slot = np.arange(k_max)[None, :]
    live = slot < deg[:, None]
    pad_idx = np.where(live, indptr[:-1, None] + slot, 0)
    groups = np.asarray(graph.groups)
    half = groups < (int(groups.max()) + 1) // 2 if groups.max() > 0 \
        else np.arange(n) < n // 2
    i32 = lambda a: jnp.asarray(np.asarray(a, np.int32))
    return RefGraph(i32(indptr), i32(indices), i32(rev), i32(deg),
                    jnp.asarray(half), i32(pad_idx), jnp.asarray(live))


class Events(NamedTuple):
    i: jnp.ndarray
    s: jnp.ndarray
    j: jnp.ndarray
    r: jnp.ndarray
    deliver_ij: jnp.ndarray
    deliver_ji: jnp.ndarray
    stale_ij: jnp.ndarray
    stale_ji: jnp.ndarray
    valid: jnp.ndarray


def rates(key, cond: dict, n: int):
    if cond["straggler_frac"] <= 0.0:
        return jnp.ones((n,), jnp.float32)
    slow = jax.random.bernoulli(key, cond["straggler_frac"], (n,))
    return jnp.where(slow, jnp.float32(cond["straggler_factor"]), 1.0)


def draw(key, cond: dict, g: RefGraph, active, rate, t, batch: int) -> Events:
    """One round's wake-ups under the fault process."""
    n = g.deg.shape[0]
    # six keys a round, in the definition's order; the sixth is spare
    kw, ks, k1, k2, k3, _ = jax.random.split(key, 6)
    cdf = jnp.cumsum(rate * active.astype(jnp.float32))
    alive = cdf[-1] > 0
    u = jax.random.uniform(kw, (batch,)) * jnp.maximum(cdf[-1], 1e-30)
    i = jnp.clip(jnp.searchsorted(cdf, u, side="right"), 0, n - 1)
    i = i.astype(jnp.int32)
    us = jax.random.uniform(ks, (batch,))
    d = g.deg[i]
    s = jnp.maximum(jnp.minimum((us * d.astype(jnp.float32))
                                .astype(jnp.int32), d - 1), 0)
    e = g.indptr[i] + s
    j = g.indices[e]
    r = g.rev[e]
    valid = alive & (d > 0)
    ok = valid & active[i] & active[j]
    if cond["partition_start"] >= 0 and \
            cond["partition_end"] > cond["partition_start"]:
        inside = (t >= cond["partition_start"]) & (t < cond["partition_end"])
        ok &= ~(inside & (g.half[i] != g.half[j]))
    if cond["drop_prob"] > 0.0:
        lost_ij = jax.random.bernoulli(k1, cond["drop_prob"], (batch,))
        lost_ji = jax.random.bernoulli(k2, cond["drop_prob"], (batch,))
    else:
        lost_ij = lost_ji = jnp.zeros((batch,), bool)
    if cond["stale_prob"] > 0.0:
        lag = jax.random.bernoulli(k3, cond["stale_prob"], (n,))
        stale_ij, stale_ji = lag[i], lag[j]
    else:
        stale_ij = stale_ji = jnp.zeros((batch,), bool)
    return Events(i, s, j, r, ok & ~lost_ij, ok & ~lost_ji, stale_ij,
                  stale_ji, valid)


def churn(key, cond: dict, active):
    if cond["churn_rate"] <= 0.0:
        return active
    flip = jax.random.bernoulli(key, cond["churn_rate"], active.shape)
    return jnp.where(flip, ~active, active)


def counts(ev: Events) -> jnp.ndarray:
    """(delivered, dropped, invalid) of one round."""
    delivered = jnp.sum(ev.deliver_ij) + jnp.sum(ev.deliver_ji)
    dropped = jnp.sum(ev.valid & ~ev.deliver_ij) \
        + jnp.sum(ev.valid & ~ev.deliver_ji)
    return jnp.stack([delivered, dropped, jnp.sum(~ev.valid)])


def round_keys(seed: int, cond: dict, n: int, rounds: int):
    """(per-agent rates, one key per round) of a call with ``seed``."""
    key, k_rate = jax.random.split(jax.random.PRNGKey(seed))
    return rates(k_rate, cond, n), jax.random.split(key, rounds)
