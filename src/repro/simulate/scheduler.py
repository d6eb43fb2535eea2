"""Vectorized event engine: batched Poisson wake-ups + network conditions.

The asynchronous model of the paper (§3.2, §4.2) is a Poisson clock per
agent; conditioned on a tick, the waking agent is drawn proportionally to
its rate.  The scheduler exploits that: one scan step draws a *batch* of B
wake-ups (a superposition of B exponential arrivals) and the engine applies
them together — collisions (two events touching the same agent in one batch)
are deterministic because all communication scatters land before any model
update reads (repro.simulate.engines).

Pluggable network conditions, all vectorized per event:

  drop_prob      — iid per *direction* message loss
  stale_prob     — delayed delivery: the receiver gets the sender's model
                   from the previous round (one-round staleness). Drawn per
                   *sender agent* per round — a lagging link lags for the
                   whole round — so duplicate events in a batch carry
                   identical payloads (deterministic scatter collisions)
  straggler_frac / straggler_factor
                 — a random fraction of agents wakes at ``factor`` x the
                   base rate (heavy-tailed activity)
  churn_rate     — per-round probability an agent toggles active/inactive;
                   inactive agents neither wake nor accept messages
  partition      — during rounds [partition_start, partition_end) every
                   message crossing the topology's two halves is dropped,
                   then the network heals

DJAM (arXiv:1803.09737) and Zantedeschi et al. (arXiv:1901.08460) analyze
exactly this regime: asynchronous personal-model updates under random
wake-ups with per-agent communication bounded by neighborhood size.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from . import spans


@dataclasses.dataclass(frozen=True)
class NetworkConditions:
    """Static (trace-time) fault model. All fields are plain python floats —
    the jitted round function closes over them as compile-time constants."""

    drop_prob: float = 0.0
    stale_prob: float = 0.0
    straggler_frac: float = 0.0
    straggler_factor: float = 0.1
    churn_rate: float = 0.0
    partition_start: int = -1     # round index; -1 = never partition
    partition_end: int = -1

    @property
    def has_partition(self) -> bool:
        """Whether a partition window [start, end) is configured."""
        return 0 <= self.partition_start < self.partition_end


class EventBatch(NamedTuple):
    """One round of wake-up events (all arrays (B,))."""

    i: jnp.ndarray            # waking agent
    s: jnp.ndarray            # chosen neighbor slot in i's row
    j: jnp.ndarray            # neighbor id  = nbr_idx[i, s]
    r: jnp.ndarray            # reverse slot = rev_slot[i, s]
    deliver_ij: jnp.ndarray   # bool: i's model reached j
    deliver_ji: jnp.ndarray   # bool: j's model reached i
    stale_ij: jnp.ndarray     # bool: delivered value is one round old
    stale_ji: jnp.ndarray
    valid: jnp.ndarray        # bool: a real wake-up (False for draws made
                              # with every agent churned out, or for a
                              # degree-0 waker) — excluded from the
                              # delivered/dropped accounting entirely
    cut: jnp.ndarray          # bool: the pair straddled an active partition
                              # window (both directions lost to the cut)
    dead: jnp.ndarray         # bool: an endpoint was churned out (both
                              # directions lost to churn unless cut first)


def straggler_rates(key, cond: NetworkConditions, n: int) -> jnp.ndarray:
    """Per-agent base wake rates: 1.0, or straggler_factor for stragglers.

    Non-uniform rates generalize the paper's unit-rate Poisson clocks
    (§3.2): conditioned on a tick, the waking agent is categorical in the
    rates, which is exactly what :func:`draw_wakeups` samples.
    """
    if cond.straggler_frac <= 0.0:
        return jnp.ones((n,), jnp.float32)
    mask = jax.random.bernoulli(key, cond.straggler_frac, (n,))
    return jnp.where(mask, jnp.float32(cond.straggler_factor), 1.0)


def draw_wakeups(key, weights, batch: int):
    """B wake-ups ~ categorical(weights) via inverse-cdf (O(n + B log n)).

    Returns ``(i, alive)``: the (B,) agent draws and a scalar bool that is
    False when the weight vector is all zero (e.g. every agent churned
    out).  In that degenerate case searchsorted lands past the end of the
    flat cdf and the clip would deterministically select agent n-1; callers
    must treat the whole batch as never-valid instead of charging those
    phantom events to an arbitrary agent.
    """
    n = weights.shape[0]
    cdf = jnp.cumsum(weights)
    alive = cdf[-1] > 0
    total = jnp.maximum(cdf[-1], 1e-30)
    u = jax.random.uniform(key, (batch,)) * total
    i = jnp.searchsorted(cdf, u, side="right")
    return jnp.clip(i, 0, n - 1).astype(jnp.int32), alive


def draw_slots(key, i, deg_count) -> jnp.ndarray:
    """Uniform neighbor slot per event (pi_i uniform over N_i — the
    neighbor-selection distribution of paper §3.2, also used for the §4.2
    edge wake-ups; the joint engines keep it frozen over the *candidate*
    slots so learned weights never perturb the event process,
    DESIGN.md §13).

    Degree-0 wakers are clamped to slot 0 instead of ``deg - 1 = -1`` (the
    negative index would wrap into the last pad slot and fabricate a
    phantom edge); ``draw_events`` marks such events invalid.
    """
    u = jax.random.uniform(key, i.shape)
    deg = deg_count[i].astype(jnp.float32)
    s = jnp.minimum((u * deg).astype(jnp.int32), deg_count[i] - 1)
    return jnp.maximum(s, 0)


def draw_events(key, cond: NetworkConditions, tabs, part_half, active,
                rates, t, batch: int) -> EventBatch:
    """Sample one round's EventBatch under the network conditions.

    tabs: DeviceTables; part_half: (n,) bool; active: (n,) bool;
    rates: (n,) f32 base rates; t: scalar round index.
    """
    kw, ks, k1, k2, k3, k4 = jax.random.split(key, 6)
    i, alive = draw_wakeups(kw, rates * active.astype(jnp.float32), batch)
    s = draw_slots(ks, i, tabs.deg_count)
    j = tabs.nbr_idx[i, s]
    r = tabs.rev_slot[i, s]

    B = i.shape[0]
    # never-valid events: the all-dead draw, or an isolated (degree-0)
    # waker — these are artifacts of the sampler, not lost messages
    valid = alive & (tabs.deg_count[i] > 0)
    ok = valid
    if cond.drop_prob > 0.0:
        drop_ij = jax.random.bernoulli(k1, cond.drop_prob, (B,))
        drop_ji = jax.random.bernoulli(k2, cond.drop_prob, (B,))
    else:
        drop_ij = drop_ji = jnp.zeros((B,), bool)
    if cond.has_partition:
        in_window = (t >= cond.partition_start) & (t < cond.partition_end)
        cut = in_window & (part_half[i] != part_half[j])
        ok &= ~cut
    else:
        cut = jnp.zeros((B,), bool)
    # an inactive endpoint kills both directions (i inactive can't happen
    # through the wake draw unless everyone is inactive; guard anyway)
    dead = ~(active[i] & active[j])
    ok &= ~dead
    if cond.stale_prob > 0.0:
        # per-sender-per-round draw: identical payload for duplicate events
        n = tabs.deg_count.shape[0]
        lagging = jax.random.bernoulli(k3, cond.stale_prob, (n,))
        stale_ij = lagging[i]
        stale_ji = lagging[j]
    else:
        stale_ij = stale_ji = jnp.zeros((B,), bool)
    return EventBatch(i, s, j, r, ok & ~drop_ij, ok & ~drop_ji,
                      stale_ij, stale_ji, valid, cut, dead)


def churn_step(key, cond: NetworkConditions, active) -> jnp.ndarray:
    """Toggle agents in/out of the network with prob churn_rate per round."""
    if cond.churn_rate <= 0.0:
        return active
    toggle = jax.random.bernoulli(key, cond.churn_rate, active.shape)
    return jnp.where(toggle, ~active, active)


class EventStream(NamedTuple):
    """A full scenario's wake-up events, materialized up front.

    The fault process (wake-ups, drops, staleness, churn) never reads model
    state, so it can be drawn once on one device and replayed by every
    shard of the partitioned engine — each shard then does zero O(n)
    sampling work per round.  All arrays are (rounds, B) except
    ``active_frac`` (rounds,), the live-agent fraction after each round's
    churn.  Field semantics match :class:`EventBatch` (whose fields must
    stay a prefix of this tuple — ``_draw_stream`` splats one into the
    other).
    """

    i: jnp.ndarray
    s: jnp.ndarray
    j: jnp.ndarray
    r: jnp.ndarray
    deliver_ij: jnp.ndarray
    deliver_ji: jnp.ndarray
    stale_ij: jnp.ndarray
    stale_ji: jnp.ndarray
    valid: jnp.ndarray
    cut: jnp.ndarray
    dead: jnp.ndarray
    active_frac: jnp.ndarray


def stream_totals(stream: EventStream) -> tuple:
    """(delivered, dropped, invalid) accounting of a materialized stream.

    Never-valid events (all-dead draws, degree-0 wakers) are excluded from
    both delivered and dropped, so for every stream

        delivered + dropped == 2 * (events - invalid).
    """
    d_ij = np.asarray(stream.deliver_ij)
    d_ji = np.asarray(stream.deliver_ji)
    valid = np.asarray(stream.valid)
    delivered = int(d_ij.sum() + d_ji.sum())
    dropped = int((valid & ~d_ij).sum() + (valid & ~d_ji).sum())
    return delivered, dropped, int((~valid).sum())


class ServeStream(NamedTuple):
    """A scenario's inference requests, materialized up front.

    The second event stream of the personalization service (DESIGN.md
    §16): request ``q`` asks for user ``user[q]``'s current personalized
    model during round ``round[q]``.  Requests are *reads* — they never
    touch model state, RNG, or the gossip event schedule — so the stream
    is drawn host-side from its own generator, entirely independent of
    :func:`precompute_event_stream`'s key schedule: a run with a serve
    stream replays the bit-identical gossip trajectory of the serve-free
    run (the acceptance property tests/test_serve_collab.py holds).

    ``round`` is sorted ascending; a request in round t is served from the
    first committed state snapshot covering t (the record chunk it falls
    in — ``chunk_of_round``), the read/write-split granularity at which
    the jitted scan publishes state (``repro.serve.store``).
    """

    user: np.ndarray     # (R,) int32 requested agent/user id
    round: np.ndarray    # (R,) int32 arrival round, sorted ascending

    @property
    def n_requests(self) -> int:
        """Total request count R."""
        return int(self.user.shape[0])


def precompute_serve_stream(n: int, rounds: int, rate: float,
                            seed: int = 0) -> ServeStream:
    """Draw ``rate`` requests/round for ``rounds`` rounds over ``n`` users.

    Uniform arrival rounds (sorted) and uniform users, from a dedicated
    ``numpy`` generator — deliberately not jax PRNG, so no accidental
    coupling with the gossip key schedule is even possible.  ``rate`` may
    be fractional; the total request count is ``round(rate * rounds)``.
    """
    if rounds < 1:
        raise ValueError(f"rounds must be >= 1, got {rounds}")
    n_req = int(round(rate * rounds))
    rng = np.random.default_rng(seed)
    t = np.sort(rng.integers(0, rounds, size=n_req)).astype(np.int32)
    user = rng.integers(0, n, size=n_req).astype(np.int32)
    return ServeStream(user=user, round=t)


def serve_chunk_requests(serve: ServeStream, n_rec: int,
                         record_every: int) -> list:
    """Split a ServeStream into per-record-chunk (user, round) slices.

    Chunk ``ci`` covers rounds ``[ci * record_every, (ci+1) * record_every)``
    — exactly the rounds whose updates the engines commit in snapshot
    ``ci`` — so every request is served from the snapshot of its own
    chunk: it observes all deliveries of that chunk (post-update
    visibility) and none of any later round.  Requests beyond the clamped
    horizon (``record_chunks`` floors it) are dropped.  Returns a list of
    ``n_rec`` (user, round) int32 array pairs.
    """
    edges = np.searchsorted(serve.round,
                            np.arange(n_rec + 1) * record_every)
    return [(serve.user[edges[ci]:edges[ci + 1]],
             serve.round[edges[ci]:edges[ci + 1]])
            for ci in range(n_rec)]


@partial(jax.jit, static_argnames=("conditions", "batch", "rounds"))
def _draw_stream(tabs, part_half, rates, keys, *,
                 conditions: NetworkConditions, batch: int, rounds: int):
    n = tabs.deg_count.shape[0]

    def step(active, inp):
        t, key = inp
        k_ev, k_churn = jax.random.split(key)
        ev = draw_events(k_ev, conditions, tabs, part_half, active, rates,
                         t, batch)
        active = churn_step(k_churn, conditions, active)
        frac = jnp.mean(active.astype(jnp.float32))
        return active, (ev, frac)

    # the whole executable is the draw phase of the rounds that replay it
    with spans.phase("draw"):
        ts = jnp.arange(rounds, dtype=jnp.int32)
        _, (evs, fracs) = jax.lax.scan(step, jnp.ones((n,), bool),
                                       (ts, keys))
    return EventStream(*evs, fracs)


def precompute_event_stream(tabs, part_half, conditions: NetworkConditions,
                            batch: int, seed: int, rounds: int) -> EventStream:
    """Draw the whole scenario's events with ``run_mp_scenario``'s exact key
    schedule (PRNGKey(seed) -> straggler split -> one key per round), so a
    replayed stream reproduces the inline engine's trajectory bit-for-bit.
    """
    key = jax.random.PRNGKey(seed)
    key, k_strag = jax.random.split(key)
    n = tabs.deg_count.shape[0]
    rates = straggler_rates(k_strag, conditions, n)
    keys = jax.random.split(key, rounds)
    return _draw_stream(tabs, part_half, rates, keys, conditions=conditions,
                        batch=batch, rounds=rounds)
