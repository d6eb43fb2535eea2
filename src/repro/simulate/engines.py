"""Sparse event-driven engines: MP gossip + CL-ADMM over padded-neighbor
state (DESIGN.md §4).

State is O(n * k * p) instead of the reference engines' O(n^2 * p):

  theta (n, p)        — each agent's own model
  K     (n, k_max, p) — K[i, s] = agent i's copy of neighbor nbr_idx[i, s]
                        (the per-op scenario round carries it flat,
                        (n * k_max, p): see ``_scenario_scan``)

Two operating modes:

* **exact** (``sparse_async_gossip`` / ``sparse_async_admm``): one event per
  scan tick, consuming the same RNG stream and the same shared slot helpers
  (``core.sparse``) as the dense references — trajectories match those of
  ``core.model_propagation.async_gossip`` / ``core.collaborative.async_admm``
  bit-for-bit given the same seed (tests/test_simulate.py).

* **scenario** (``run_mp_scenario`` / ``run_cl_scenario`` /
  ``run_joint_scenario``): batched wake-ups from the scheduler with message
  drops, staleness, stragglers, churn and partitions.  All communication
  scatters of a round land before any model update reads, so batch
  collisions are deterministic (duplicate updates compute identical values
  from the same post-communication state).  The joint engine additionally
  re-estimates the collaboration graph online (DESIGN.md §13).
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.graph_learning import prune_rows, reweight_rows
from repro.core.losses import AgentData
from repro.core.sparse import (batched_admm_primal, batched_model_update,
                               live_slots, neighbor_aggregate,
                               quadratic_primal_core, record_chunks,
                               sample_event)
from repro.kernels.dispatch import (ReproBackend, decode_slots,
                                    encode_slots, resolve, round_prefetch,
                                    round_scales, round_stale_src)
from repro.telemetry import metrics as tmetrics
from repro.telemetry.config import TelemetryConfig, telemetry_on
from repro.telemetry.frames import TelemetryFrames
from . import scheduler as sched
from . import spans
from .scheduler import (EventStream, NetworkConditions,
                        precompute_event_stream, stream_totals)
from .topology import SparseTopology


# ---------------------------------------------------------------------------
# Exact sparse MP gossip (mirrors core.model_propagation.async_gossip)
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class SparseTrace:
    """theta_hist: (n_records, n, p); comms_hist: cumulative pairwise comms."""

    theta_hist: np.ndarray
    comms_hist: np.ndarray
    final_theta: np.ndarray
    final_knowledge: np.ndarray   # (n, k_max, p) neighbor slots


def _mp_warm_start(tabs, theta_sol):
    """Solitary models everywhere the agent has knowledge (paper §3.2)."""
    theta = theta_sol
    K = theta_sol[tabs.nbr_idx]              # (n, k_max, p)
    return theta, K


@partial(jax.jit, static_argnames=("steps", "record_every", "backend"))
def _sparse_async_scan(nbr_idx, nbr_p, slot_cdf, deg_count, rev_slot,
                       theta_sol, c, alpha, key, steps, record_every,
                       theta0, K0, backend=None):
    n, p = theta0.shape
    abar = 1.0 - alpha

    def local_update(theta, K, l, tgt):
        agg = neighbor_aggregate(nbr_p[l], K[l], backend)
        new = (alpha * agg + abar * c[l] * theta_sol[l]) / (alpha + abar * c[l])
        return theta.at[tgt].set(new, mode="drop")  # scatter: unique targets

    def step(carry, key):
        theta, K = carry
        i, s = sample_event(key, n, slot_cdf, deg_count)
        # a degree-0 waker is a no-op: redirect every scatter out of bounds
        # (dropped) instead of letting the clamped slot fabricate an edge
        valid = deg_count[i] > 0
        j = nbr_idx[i, s]
        r = rev_slot[i, s]
        ti = jnp.where(valid, i, n)
        tj = jnp.where(valid, j, n)
        # communication step: exchange current self-models
        K = K.at[ti, s].set(theta[j], mode="drop")  # scatter: unique targets
        K = K.at[tj, r].set(theta[i], mode="drop")  # scatter: unique targets
        # update step for both endpoints
        theta = local_update(theta, K, i, ti)
        theta = local_update(theta, K, j, tj)
        return (theta, K), theta if record_every == 1 else None

    if record_every == 1:
        keys = jax.random.split(key, steps)
        (theta, K), hist = jax.lax.scan(step, (theta0, K0), keys)
        return theta, K, hist

    # repro-lint: disable=RPL007  callers normalize via core.sparse.record_chunks
    n_rec = steps // record_every

    def outer(carry, key):
        keys = jax.random.split(key, record_every)
        carry, _ = jax.lax.scan(lambda c_, k: (step(c_, k)[0], None),
                                carry, keys)
        return carry, carry[0]

    keys = jax.random.split(key, n_rec)
    (theta, K), hist = jax.lax.scan(outer, (theta0, K0), keys)
    return theta, K, hist


def sparse_async_gossip(topo: SparseTopology, theta_sol, c, alpha: float,
                        steps: int, seed: int = 0, record_every: int = 100,
                        backend: Optional[ReproBackend] = None) -> SparseTrace:
    """The paper's async gossip MP algorithm over O(n k p) sparse state.

    Bit-for-bit equal to ``core.model_propagation.async_gossip`` for the same
    (graph, seed) — same RNG stream, same shared slot arithmetic — while
    scaling to tens of thousands of agents.  The horizon follows the shared
    recording policy (``core.sparse.record_chunks``): floored to a whole
    number of record chunks, never zero.
    """
    tabs = topo.device_tables()
    n = topo.n
    theta_sol = jnp.asarray(theta_sol, jnp.float32).reshape(n, -1)
    c = jnp.asarray(c, jnp.float32)
    theta0, K0 = _mp_warm_start(tabs, theta_sol)
    key = jax.random.PRNGKey(seed)
    record_every, n_rec = record_chunks(steps, record_every)
    theta, K, hist = _sparse_async_scan(
        tabs.nbr_idx, tabs.nbr_p, tabs.slot_cdf, tabs.deg_count,
        tabs.rev_slot, theta_sol, c, alpha, key, n_rec * record_every,
        record_every, theta0, K0, backend)
    comms = 2 * record_every * (np.arange(hist.shape[0]) + 1)
    return SparseTrace(np.asarray(hist), comms, np.asarray(theta),
                       np.asarray(K))


# ---------------------------------------------------------------------------
# Synchronous sparse sweep (Eq. 5 over CSR) — the gather-mix hot loop
# ---------------------------------------------------------------------------


def sparse_sync_mp(topo: SparseTopology, theta_sol, c, alpha: float,
                   sweeps: int, use_kernel: bool = False,
                   backend: Optional[ReproBackend] = None) -> jnp.ndarray:
    """Fixed-point iteration Eq. (5) over the sparse neighbor layout.

    theta_{t+1}[i] = (alpha * sum_s P[i,s] theta_t[nbr[i,s]]
                      + (1-alpha) c_i theta_sol[i]) / (alpha + (1-alpha) c_i)

    One sweep = one "sparse_mix" op (O(n * k * p) gather-mix over all
    agents), resolved through ``kernels.dispatch`` (fused XLA take/einsum
    by default).  ``use_kernel=True`` is the deprecated spelling of
    ``backend=ReproBackend.using(sparse_mix="pallas_sparse")``; off-TPU it
    needs the interpret opt-in (``REPRO_PALLAS_INTERPRET=1``) like any
    explicit Pallas request.
    """
    from repro.core.model_propagation import mp_mix_operator
    tabs = topo.device_tables()
    n = topo.n
    theta_sol = jnp.asarray(theta_sol, jnp.float32).reshape(n, -1)
    c = jnp.asarray(c, jnp.float32)
    # (n, k) mixing slot weights + (n,) anchor coefficients
    w, b = mp_mix_operator(tabs.nbr_p, c, alpha)

    if use_kernel and backend is None:
        backend = ReproBackend.using(sparse_mix="pallas_sparse")
    mix = resolve("sparse_mix", backend)

    def sweep(theta, _):
        return mix(theta, tabs.nbr_idx, w, b, theta_sol), None

    theta, _ = jax.lax.scan(jax.jit(sweep), theta_sol, None, length=sweeps)
    return theta


# ---------------------------------------------------------------------------
# Scenario engine: batched wake-ups + network conditions (MP gossip)
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class SimTrace:
    """Result of a scenario run.

    theta_hist:   (n_records, n, p)
    active_hist:  (n_records,) fraction of live agents
    delivered:    total messages delivered;  dropped: total lost
    rounds, events: totals (events = wake-ups = 2 attempted messages each)
    invalid:      never-valid wake-ups (all-dead draws, degree-0 wakers) —
                  excluded from delivered AND dropped, so the accounting
                  invariant is  delivered + dropped == 2 * (events - invalid)
    telemetry:    TelemetryFrames when the run was launched with
                  ``TelemetryConfig(enabled=True)``, else None
    serve:        ``repro.serve.ServeReport`` when the run carried an
                  inference-request stream (``ScenarioSpec.serve``), else
                  None — serving reads committed snapshots only, so its
                  presence never changes theta_hist (DESIGN.md §16)
    """

    theta_hist: np.ndarray
    active_hist: np.ndarray
    delivered: int
    dropped: int
    rounds: int
    events: int
    invalid: int = 0
    telemetry: Optional[TelemetryFrames] = None
    serve: Optional[object] = None


@partial(jax.jit, static_argnames=("conditions", "alpha", "batch",
                                   "record_every", "n_rec", "tel",
                                   "backend"))
def _scenario_scan(tabs, part_half, rates, theta_sol, c, keys, ts, *,
                   conditions: NetworkConditions, alpha: float, batch: int,
                   record_every: int, n_rec: int, tel: bool = False,
                   backend: Optional[ReproBackend] = None):
    """Module-level jitted runner so repeated calls with the same static
    (conditions, alpha, batch, record_every, n_rec) and shapes hit the jit
    cache — benchmark warmups genuinely pre-compile the timed run.

    ``tel`` (static) appends the telemetry accumulators — per-agent
    staleness counters, applied-update and drop-cause counters — to the
    carry and per-chunk objective/staleness snapshots to the outputs; at
    the default False the traced program is exactly the pre-telemetry
    scan (the ``*tstate`` unpacking leaves the carry a 7-tuple).

    ``backend`` (static) opts in to the fused ``round_step`` op
    (kernels/round_fuse.py): the carry threads the flat slot table, the
    software-pipelined prefetch of the *next* round's events/operands
    (drawn at the end of each round, after its scatters), and the per-row
    first-receipt flags.  The caller passes the plain unshifted keys; the
    keys are shifted one round ahead internally so the carried prefetch
    consumes the bitwise-identical RNG stream.  At the default None the
    traced program is the historic per-op gather/mix/scatter sequence,
    over a slot table carried flat: ``(n * k_max, p)``, agent i's slot s
    at row ``i * k_max + s``.  Its two slot scatters write that layout,
    and the update gathers each waker's k_max rows from it.  On a TPU an
    f32 array is tiled (8, 128) over its last two dims, so for a k_max
    that is no multiple of 8 an ``(n, k_max, p)`` carry is no bitcast of
    the scatters' layout: it was relaid out into it and back every round,
    a copy of the whole table each way.  On the CPU the two layouts are
    the same bytes and the trajectory is bit for bit the 3-D one's.

    Both round bodies build their carry (warm start included) here, in-jit,
    from ``theta_sol``: the ~n*k*p slot table is then a temporary the scan
    updates in place, never an argument buffer it would have to copy."""
    n = theta_sol.shape[0]
    fused = backend is not None
    step_fn = resolve("round_step", backend) if fused else None
    km = tabs.nbr_idx.shape[1]
    no_stale = conditions.stale_prob == 0.0
    with spans.phase("init"):
        theta0, K0 = _mp_warm_start(tabs, theta_sol)
        if fused:
            a_w = round_scales(tabs.nbr_p, c, alpha=alpha)
            theta_base = batched_model_update(tabs.nbr_p, K0, c, theta_sol,
                                              alpha)
            # build the fused carry (slot table, round 0's prefetch from
            # the unshifted first key, first-receipt flags); the scan then
            # consumes the keys shifted one round ahead (the last key's
            # second draw is discarded), so the carried prefetch sees the
            # bitwise-identical RNG stream
            active0 = jnp.ones((n,), bool)
            Ke0 = encode_slots(K0)
            flat = keys.reshape(-1, 2)
            k_ev, k_churn = jax.random.split(flat[0])
            ev0 = sched.draw_events(k_ev, conditions, tabs, part_half,
                                    active0, rates, 0, batch)
            pf0 = (ev0,) + round_prefetch(
                theta0, theta0, Ke0, ev0.i, ev0.j, ev0.s, ev0.r,
                ev0.deliver_ij, ev0.deliver_ji, ev0.stale_ij, ev0.stale_ji,
                no_stale=no_stale) + (k_churn,)
            keys = jnp.concatenate([flat[1:], flat[-1:]]).reshape(
                n_rec, record_every, 2)
            carry0 = (theta0, Ke0, pf0, active0, jnp.int32(0), jnp.int32(0),
                      jnp.int32(0), jnp.zeros((n,), bool))
        else:
            # the slot table flat, row i*km + s (see the docstring)
            carry0 = (theta0, K0.reshape(n * km, -1), theta0,
                      jnp.ones((n,), bool), jnp.int32(0), jnp.int32(0),
                      jnp.int32(0))
        if tel:
            carry0 = carry0 + (jnp.zeros((n,), jnp.int32), jnp.int32(0),
                               jnp.int32(0), jnp.int32(0), jnp.int32(0))

    def round_fn(carry, inp):
        t, key = inp
        if fused:
            # key is the *next* round's key; this round's events and churn
            # key arrive pre-drawn in the carried prefetch
            theta_in, K, pf, active, delivered, dropped, invalid, \
                got_ever, *tstate = carry
            ev, msg, tgt_row, enc, k_old, k_churn = pf
            # round t+1's events depend only on RNG and the post-churn
            # active set — never on theta — so draw them and gather their
            # stale-message source from theta_in BEFORE this round's
            # scatters.  Once that gather is theta_in's last read, XLA
            # scatters theta in place instead of copying the model table
            # every round (~25% of the fused round on CPU at n=10k); the
            # barrier pins the gather-before-scatter order.
            with spans.phase("draw"):
                active2 = sched.churn_step(k_churn, conditions, active)
                k_ev2, k_churn2 = jax.random.split(key)
                ev2 = sched.draw_events(k_ev2, conditions, tabs, part_half,
                                        active2, rates, t + 1, batch)
            if no_stale:
                # zero staleness (static): no previous-model reads at all,
                # so the step is already theta_in's last consumer
                stale_src = None
            else:
                with spans.phase("comm"):
                    stale_src = round_stale_src(theta_in, ev2.i, ev2.j)
                    theta_in, stale_src = jax.lax.optimization_barrier(
                        (theta_in, stale_src))
            with spans.phase("update"):
                theta, K, got_ever, _ = step_fn(theta_in, K, got_ever, msg,
                                                tgt_row, enc, k_old,
                                                theta_base, a_w)
        else:
            theta, K, theta_prev, active, delivered, dropped, invalid, \
                *tstate = carry
            theta_in = theta              # next round's "one-round-old" model
            with spans.phase("draw"):
                k_ev, k_churn = jax.random.split(key)
                ev = sched.draw_events(k_ev, conditions, tabs, part_half,
                                       active, rates, t, batch)
        with spans.phase("update"):
            upd = jnp.concatenate([ev.i, ev.j])                  # (2B,)
            got = jnp.concatenate([ev.deliver_ji, ev.deliver_ij])
            got &= active[upd]

        if not fused:
            # --- communication: all scatters land before any update reads
            with spans.phase("comm"):
                msg_i = jnp.where(ev.stale_ij[:, None], theta_prev[ev.i],
                                  theta[ev.i])
                msg_j = jnp.where(ev.stale_ji[:, None], theta_prev[ev.j],
                                  theta[ev.j])
                # undelivered messages keep row n, so their flat row
                # n*km + slot is out of bounds -> dropped
                row_j = jnp.where(ev.deliver_ij, ev.j, n)
                row_i = jnp.where(ev.deliver_ji, ev.i, n)
                # scatter: last-write-wins — a repeated edge in one batch
                # lands the batch-order winner; kernels/round_fuse.
                # round_step dedups to the same winner so both paths agree
                # bit-for-bit
                K = K.at[row_j * km + ev.r].set(msg_i, mode="drop")
                # scatter: last-write-wins
                K = K.at[row_i * km + ev.s].set(msg_j, mode="drop")

            # --- update: endpoints that received a message recompute
            # Eq. (6) via the shared per-shard step
            # (core.sparse.batched_model_update — the same function the
            # partitioned engine applies to local rows)
            with spans.phase("update"):
                # the updaters' km rows each: (2B, km) flat indices
                slots = upd[:, None] * km + jnp.arange(km)
                K_upd = K[slots.reshape(-1)].reshape(*slots.shape, -1)
                new = batched_model_update(tabs.nbr_p[upd], K_upd, c[upd],
                                           theta_sol[upd], alpha)
                # scatter: idempotent — duplicate agents in upd recompute
                # the same row from the same post-communication K
                theta = theta.at[jnp.where(got, upd, n)].set(new,
                                                             mode="drop")

        with spans.phase("draw"):
            delivered = delivered + jnp.sum(ev.deliver_ij) \
                + jnp.sum(ev.deliver_ji)
            dropped = dropped + jnp.sum(ev.valid & ~ev.deliver_ij) \
                + jnp.sum(ev.valid & ~ev.deliver_ji)
            invalid = invalid + jnp.sum(~ev.valid)
            active = active2 if fused \
                else sched.churn_step(k_churn, conditions, active)
        if tel:
            with spans.phase("record"):
                stale, updates, d_link, d_churn, d_part = tstate
                stale = tmetrics.staleness_step(stale, got, upd, n)
                updates = updates + jnp.sum(got)
                link, churn, part = tmetrics.batch_drop_causes(
                    ev.deliver_ij, ev.deliver_ji, ev.valid, ev.cut, ev.dead)
                tstate = (stale, updates, d_link + link, d_churn + churn,
                          d_part + part)
        if fused:
            # --- finish round t+1's prefetch: its stale-message gather ran
            # pre-scatter (above); the fresh-model and k_old gathers must
            # run here, *after* this round's scatters (the placement the
            # pipelined layout exists for)
            with spans.phase("comm"):
                pf = (ev2,) + round_prefetch(
                    theta, theta_in, K, ev2.i, ev2.j, ev2.s, ev2.r,
                    ev2.deliver_ij, ev2.deliver_ji, ev2.stale_ij,
                    ev2.stale_ji, stale_src=stale_src,
                    no_stale=no_stale) + (k_churn2,)
            base = (theta, K, pf, active, delivered, dropped, invalid,
                    got_ever)
        else:
            base = (theta, K, theta_in, active, delivered, dropped, invalid)
        return base + tuple(tstate), None

    def outer(carry, inp):
        ks, t0 = inp
        with spans.phase("draw"):
            inner_ts = t0 + jnp.arange(record_every)
        carry, _ = jax.lax.scan(round_fn, carry, (inner_ts, ks))
        with spans.phase("record"):
            frac = jnp.mean(carry[3].astype(jnp.float32))
            if tel:
                theta, K = carry[0], carry[1]
                K = decode_slots(K, km) if fused \
                    else K.reshape(n, km, -1)
                obj = tmetrics.mp_local_objective(theta, K, tabs.nbr_p, c,
                                                  theta_sol, alpha)
                stale, updates, d_link, d_churn, d_part = \
                    carry[8 if fused else 7:]
                return carry, (theta, frac, obj, stale, updates, carry[4],
                               d_link, d_churn, d_part, carry[6])
        return carry, (carry[0], frac)

    carry, outs = jax.lax.scan(outer, carry0, (keys, ts))
    # only the event counters leave the scan: returning the final slot
    # table would keep a second ~n*k*p buffer alive as a program output
    return carry[4:7], outs


def run_mp_scenario(topo: SparseTopology, theta_sol, c, alpha: float,
                    conditions: NetworkConditions, rounds: int,
                    batch: int, seed: int = 0, record_every: int = 10,
                    telemetry: Optional[TelemetryConfig] = None,
                    backend: Optional[ReproBackend] = None) -> SimTrace:
    """MP gossip under a fault scenario, B wake-ups per round.

    Per round: draw an EventBatch, land every delivered message (scatter into
    the receivers' neighbor slots; stale deliveries read the sender's model
    from the previous round), then every endpoint that received something
    recomputes its model from its post-communication slots (update step
    Eq. 6).  Inactive (churned-out) agents neither wake nor update.  The
    slots live in a flat ``(n * k_max, p)`` table, row ``i * k_max + s``
    (the layout the scatters write, so a TPU's (8, 128) tiling does not
    relay the table out and back each round; ``_scenario_scan``).

    The horizon is floored to a multiple of record_every (record_every is
    clamped to ``rounds`` first); SimTrace.rounds reports the actual count.
    ``telemetry=TelemetryConfig(enabled=True)`` additionally accumulates
    the DESIGN.md §14 metrics inside the scan carry and attaches them as
    ``SimTrace.telemetry``; the default leaves the compiled program — and
    the trajectory — exactly as without the argument.

    ``backend`` opts in to the fused ``round_step`` round body
    (kernels/round_fuse.py; auto resolves the fused XLA form on every
    platform).  The fused path carries a flat id-column slot
    table, telescopes the Eq. 6 update from scattered slot deltas, and
    software-pipelines the next round's event draw + operand gathers
    behind the current round's scatters — the same RNG stream and event
    sequence, so counters match the default path exactly and the
    trajectory agrees to fp rounding (not bit-for-bit); ``backend=None``
    keeps the historic program exactly.
    """
    n = topo.n
    record_every, n_rec = record_chunks(rounds, record_every)
    tel = telemetry_on(telemetry)
    with spans.host("prepare") as span:
        halves = topo.partition_halves()
        ts = (np.arange(n_rec) * record_every).astype(np.int32)
        span.set_metadata(bytes=spans.upload_bytes(
            topo.tables, theta_sol, c, halves, ts))
        tabs = topo.device_tables()
        theta_sol = jnp.asarray(theta_sol, jnp.float32).reshape(n, -1)
        c = jnp.asarray(c, jnp.float32)
        part_half = jnp.asarray(halves)
        key = jax.random.PRNGKey(seed)
        key, k_strag = jax.random.split(key)
        rates = sched.straggler_rates(k_strag, conditions, n)
        keys = jax.random.split(key, n_rec * record_every).reshape(
            n_rec, record_every, 2)
        ts = jnp.asarray(ts)
    with spans.host("scan"):
        counters, outs = _scenario_scan(
            tabs, part_half, rates, theta_sol, c, keys, ts,
            conditions=conditions, alpha=alpha, batch=batch,
            record_every=record_every, n_rec=n_rec, tel=tel,
            backend=backend)
    with spans.host("wait"):
        jax.block_until_ready((counters, outs))
    with spans.host("fetch", bytes=spans.fetch_bytes(counters, outs)):
        delivered, dropped, invalid = counters
        total_rounds = n_rec * record_every
        frames = None
        if tel:
            (hist, active_hist, obj_h, stale_h, upd_h, del_h, link_h,
             churn_h, part_h, inv_h) = outs
            frames = TelemetryFrames(
                rounds=(np.arange(n_rec) + 1) * record_every,
                objective=np.asarray(obj_h), staleness=np.asarray(stale_h),
                updates=np.asarray(upd_h, np.int64),
                delivered=np.asarray(del_h, np.int64),
                drop_link=np.asarray(link_h, np.int64),
                drop_churn=np.asarray(churn_h, np.int64),
                drop_partition=np.asarray(part_h, np.int64),
                invalid=np.asarray(inv_h, np.int64))
        else:
            hist, active_hist = outs
        return SimTrace(np.asarray(hist), np.asarray(active_hist),
                        int(delivered), int(dropped), total_rounds,
                        total_rounds * batch, int(invalid), telemetry=frames)


# ---------------------------------------------------------------------------
# Exact sparse CL-ADMM (mirrors core.collaborative.async_admm, quadratic)
# ---------------------------------------------------------------------------


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class SparseADMMState:
    """Sparse partial-consensus state: per-agent self model + per-slot
    copies/secondary/dual variables (all neighbor arrays (n, k_max, p))."""

    theta: jnp.ndarray
    K: jnp.ndarray
    Z_own: jnp.ndarray
    Z_nbr: jnp.ndarray
    L_own: jnp.ndarray
    L_nbr: jnp.ndarray


def init_sparse_admm(topo: SparseTopology, theta_sol) -> SparseADMMState:
    """Warm start (paper §4.2): share solitary models with neighbors."""
    theta_sol = jnp.asarray(theta_sol, jnp.float32).reshape(topo.n, -1)
    return _warm_admm_state(topo.device_tables().nbr_idx, theta_sol)


def _warm_admm_state(nbr_idx, theta_sol) -> SparseADMMState:
    """:func:`init_sparse_admm` on device arrays (traceable)."""
    return warm_admm_from_copies(theta_sol, theta_sol[nbr_idx])


def warm_admm_from_copies(theta, K) -> SparseADMMState:
    """The warm-start rule, from the models ``theta`` (n, p) and each
    agent's copies ``K`` (n, k, p) of its neighbours' models: Z_own holds
    the own model in every slot, Z_nbr the neighbour copies, both duals
    start at zero (traceable; the sharded engine applies it per shard)."""
    n, k, p = K.shape
    K = K.astype(jnp.float32)
    Z_own = jnp.broadcast_to(theta[:, None, :], (n, k, p)).astype(jnp.float32)
    zeros = jnp.zeros((n, k, p), jnp.float32)
    return SparseADMMState(theta, K, Z_own, K, zeros, zeros)


def _sparse_primal_quadratic(st: SparseADMMState, l, nbr_w, deg_count, D,
                             mu, rho, data: AgentData,
                             backend=None) -> SparseADMMState:
    """Slot-row mirror of core.collaborative._primal_quadratic."""
    k = nbr_w.shape[1]
    live = jnp.arange(k) < deg_count[l]
    w = nbr_w[l]
    m_l = jnp.sum(data.mask[l])
    sx = jnp.sum(data.x[l] * data.mask[l][:, None], axis=0)
    theta_l, theta_js = quadratic_primal_core(
        w, live, st.Z_own[l], st.Z_nbr[l], st.L_own[l], st.L_nbr[l],
        D[l], m_l, sx, mu, rho, backend)
    # scatter: unique targets (scalar index l)
    K = st.K.at[l].set(jnp.where(live[:, None], theta_js, st.K[l]))
    theta = st.theta.at[l].set(theta_l)  # scatter: unique target (scalar index l)
    return SparseADMMState(theta, K, st.Z_own, st.Z_nbr, st.L_own, st.L_nbr)


def _sparse_edge_zl(st: SparseADMMState, i, s, j, r, rho) -> SparseADMMState:
    """Slot mirror of core.collaborative._edge_zl_update for edge (i, j):
    slot s is j's position in i's row, slot r is i's position in j's row."""
    z_i = 0.5 * ((st.L_own[i, s] + st.L_nbr[j, r]) / rho
                 + st.theta[i] + st.K[j, r])
    z_j = 0.5 * ((st.L_own[j, r] + st.L_nbr[i, s]) / rho
                 + st.theta[j] + st.K[i, s])
    # scatter: unique targets — (i, s) and (j, r) are the two directed
    # slots of one edge, distinct cells by construction
    Z_own = st.Z_own.at[i, s].set(z_i).at[j, r].set(z_j)
    Z_nbr = st.Z_nbr.at[i, s].set(z_j).at[j, r].set(z_i)  # scatter: unique targets
    L_own = st.L_own.at[i, s].add(rho * (st.theta[i] - z_i))
    L_own = L_own.at[j, r].add(rho * (st.theta[j] - z_j))
    L_nbr = st.L_nbr.at[i, s].add(rho * (st.K[i, s] - z_j))
    L_nbr = L_nbr.at[j, r].add(rho * (st.K[j, r] - z_i))
    return SparseADMMState(st.theta, st.K, Z_own, Z_nbr, L_own, L_nbr)


@dataclasses.dataclass
class SparseCLTrace:
    """Recorded sparse CL-ADMM trajectory (models, comms, final state)."""

    theta_hist: np.ndarray
    comms_hist: np.ndarray
    final: SparseADMMState


def sparse_async_admm(topo: SparseTopology, data: AgentData, mu: float,
                      rho: float, steps: int = 1000, seed: int = 0,
                      record_every: int = 50, theta_sol=None,
                      state: Optional[SparseADMMState] = None,
                      backend: Optional[ReproBackend] = None) -> SparseCLTrace:
    """Asynchronous decentralized CL-ADMM (paper §4.2) over sparse edge state.

    Quadratic loss only (exact closed-form primal).  Bit-for-bit equal to
    ``core.collaborative.async_admm(..., loss="quadratic")`` for the same
    (graph, seed) while storing O(n k p) instead of 5 x O(n^2 p).
    """
    tabs = topo.device_tables()
    n = topo.n
    D = jnp.asarray(tabs.deg_w, jnp.float32)
    if state is None:
        if theta_sol is None:
            raise ValueError("need theta_sol (warm start) or explicit state")
        state = init_sparse_admm(topo, theta_sol)

    def tick(st: SparseADMMState, key):
        i, s = sample_event(key, n, tabs.slot_cdf, tabs.deg_count)
        # degree-0 waker -> no-op: out-of-bounds targets drop every scatter
        valid = tabs.deg_count[i] > 0
        ti = jnp.where(valid, i, n)
        tj = jnp.where(valid, tabs.nbr_idx[i, s], n)
        r = tabs.rev_slot[i, s]
        st = _sparse_primal_quadratic(st, ti, tabs.nbr_w, tabs.deg_count, D,
                                      mu, rho, data, backend)
        st = _sparse_primal_quadratic(st, tj, tabs.nbr_w, tabs.deg_count, D,
                                      mu, rho, data, backend)
        return _sparse_edge_zl(st, ti, s, tj, r, rho)

    record_every, n_rec = record_chunks(steps, record_every)

    @jax.jit
    def run(state, key):
        def outer(st, key):
            keys = jax.random.split(key, record_every)
            st = jax.lax.scan(lambda s_, k: (tick(s_, k), None), st, keys)[0]
            return st, st.theta
        keys = jax.random.split(key, n_rec)
        return jax.lax.scan(outer, state, keys)

    final, hist = run(state, jax.random.PRNGKey(seed))
    comms = 2 * record_every * (np.arange(n_rec) + 1)
    return SparseCLTrace(np.asarray(hist), comms, final)


# ---------------------------------------------------------------------------
# Scenario engine: batched wake-ups + network conditions (CL-ADMM)
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class CLSimTrace(SimTrace):
    """SimTrace plus the final sparse ADMM state (single-device runs)."""

    final: Optional[SparseADMMState] = None


def _reshape_stream(stream: EventStream, n_rec: int, record_every: int):
    """(rounds, B) event arrays -> (n_rec, record_every, B) scan blocks."""
    return jax.tree_util.tree_map(
        lambda x: x.reshape(n_rec, record_every, *x.shape[1:]),
        stream._replace(active_frac=None))


def _fetch_stream_totals(stream, n_rec: int, record_every: int) -> tuple:
    """(delivered, dropped, invalid, active_hist) of a replayed stream,
    copied back under their own ``fetch`` span (they are ready before the
    scan that replays the stream is)."""
    with spans.host("fetch", bytes=spans.fetch_bytes(
            stream.deliver_ij, stream.deliver_ji, stream.valid,
            stream.active_frac)):
        totals = stream_totals(stream)
        active_hist = np.asarray(stream.active_frac).reshape(
            n_rec, record_every)[:, -1]
    return totals + (active_hist,)


@partial(jax.jit, static_argnames=("mu", "rho", "backend", "tel", "primal"))
def _cl_scenario_scan(nbr_w, deg_count, D, m_counts, sx, state0, ev,
                      tel_args=(), xym=(), *, mu: float, rho: float,
                      backend=None, tel: bool = False, primal=None):
    """Batched-event CL-ADMM rounds over a precomputed event stream.

    One round = one (record_every-chunked) EventStream slice of B wake-ups:

    1. **primal phase** — every endpoint that completed its half of the
       handshake (its partner's payload was delivered and both ends are
       active, all folded into the stream's deliver flags) recomputes its
       exact quadratic primal from its own round-start rows
       (``core.sparse.batched_admm_primal``) and rewrites theta + its K row.
       Duplicate endpoints in a batch read identical round-start state and
       scatter identical values, so collisions are deterministic.
    2. **publish** — the round's payload is (post-primal theta and K,
       round-start duals); the previous round's payload serves the
       one-round-stale deliveries (same convention as the MP engine).
       The stream fixes every round's partner cells ``(oth_a, oth_s)``
       before the scan, so the carry holds only the (2B, p) payload rows
       the next rounds read, never a whole-state snapshot: round t
       gathers round t+1's stale model rows after its primal scatters,
       and after its edge scatters round t+1's fresh dual rows and round
       t+2's stale ones (round t+1's round-start duals, carried one round
       more).  A snapshot, or a partner read of L_own / L_nbr in the round
       that scatters them, held live across those scatters would make XLA
       copy the whole (n, k, p) arrays every round.
    3. **edge phase** — each delivered direction updates the *receiver's*
       (Z_own, Z_nbr, L_own, L_nbr) slots via the fused ``cl_edge_step``
       op (kernels/round_fuse.py; the ``admm_edge_halfstep`` math) from
       its own post-primal values and the partner's payload.  With both
       directions fresh this is exactly ``_sparse_edge_zl``; a dropped
       direction leaves that side's edge copies untouched (the mirrored
       copies may diverge — the asynchronous regime of DJAM,
       arXiv:1803.09737).

    ``tel`` (static) appends staleness/update accumulators to the carry
    and per-chunk (objective, staleness, updates) snapshots to the
    history; ``tel_args`` then carries the extra sufficient statistic
    (sxx,) the Eq. 7 objective needs.  At the default False the traced
    program is exactly the pre-telemetry scan.

    ``primal`` (static) is a PrimalSolver (``core.primal``); ``None``
    keeps the inline exact quadratic solve — the identical traced program
    the scan ran before primal solvers were pluggable.  A data-hungry
    solver (``primal.needs_data``) additionally receives the rows' padded
    local datasets via ``xym = (x, y, mask)``.

    ``state0`` is a :class:`SparseADMMState`, or ``(nbr_idx, theta_sol)``
    to build the :func:`init_sparse_admm` warm start here, in-jit: the
    (n, k, p) state is then a temporary the scan updates in place, never
    an argument buffer it would have to copy.
    """
    n, k = nbr_w.shape
    if not isinstance(state0, SparseADMMState):
        with spans.phase("init"):
            state0 = _warm_admm_state(*state0)
    edge_fn = resolve("cl_edge_step", backend)
    # each round's partner cells, and those of the two rounds after it (the
    # last rounds' lookahead wraps to round 0 and is never read)
    n_rec, re_ = ev.i.shape[:2]
    cells = [jnp.concatenate([a, b], axis=-1).reshape(n_rec * re_, -1)
             for a, b in ((ev.j, ev.i), (ev.r, ev.s))]
    ahead = tuple(jnp.roll(c, -d, axis=0).reshape(n_rec, re_, -1)
                  for d in (1, 2) for c in cells)   # (a1, s1, a2, s2)

    def round_fn(carry, xs):
        ev_t, (a1, s1, a2, s2) = xs
        st, pay, *tstate = carry
        pv_th, pv_K, pv_Lo, pv_Ln, f_Lo, f_Ln, nx_Lo, nx_Ln = pay
        # --- primal phase: endpoints whose incoming payload was delivered
        with spans.phase("primal"):
            upd = jnp.concatenate([ev_t.i, ev_t.j])                # (2B,)
            got = jnp.concatenate([ev_t.deliver_ji, ev_t.deliver_ij])
            live_rows = jnp.arange(k)[None, :] < deg_count[upd][:, None]
            if primal is None:
                new_theta, theta_js = batched_admm_primal(
                    nbr_w[upd], live_rows, st.Z_own[upd], st.Z_nbr[upd],
                    st.L_own[upd], st.L_nbr[upd], D[upd], m_counts[upd],
                    sx[upd], mu, rho, backend)
            else:
                xr = tuple(a[upd] for a in xym) if primal.needs_data else ()
                new_theta, theta_js = primal.solve_batch(
                    nbr_w[upd], live_rows, st.Z_own[upd], st.Z_nbr[upd],
                    st.L_own[upd], st.L_nbr[upd], D[upd], m_counts[upd],
                    sx[upd], xr, st.theta[upd], mu, rho, backend)
            new_K = jnp.where(live_rows[:, :, None], theta_js, st.K[upd])
            rowu = jnp.where(got, upd, n)
            # scatter: idempotent — duplicate agents in upd derive
            # identical rows from the same round-start Z/L state
            theta = st.theta.at[rowu].set(new_theta, mode="drop")
            K = st.K.at[rowu].set(new_K, mode="drop")  # scatter: idempotent
            # publish: the post-primal models are round t+1's stale rows
            pub_th, pub_K = theta[a1], K[a1, s1]

        # --- edge phase: one half-step per delivered direction, as one
        # fused op (kernels/round_fuse.cl_edge_step — auto resolves the
        # expression-identical XLA form, so the trajectory is bit-for-bit
        # the inline code's)
        with spans.phase("edge"):
            own_s = jnp.concatenate([ev_t.s, ev_t.r])
            oth_a = jnp.concatenate([ev_t.j, ev_t.i])
            oth_s = jnp.concatenate([ev_t.r, ev_t.s])
            stale = jnp.concatenate([ev_t.stale_ji, ev_t.stale_ij])
            Z_own, Z_nbr, L_own, L_nbr = edge_fn(
                theta, K, st.Z_own, st.Z_nbr, st.L_own, st.L_nbr,
                pv_th, pv_K, pv_Lo, pv_Ln, f_Lo, f_Ln,
                upd, own_s, oth_a, oth_s, stale, got, rho=rho)
            # the post-edge duals are round t+1's round-start duals: its
            # fresh rows, and round t+2's stale ones
            pay = (pub_th, pub_K, nx_Lo, nx_Ln, L_own[a1, s1],
                   L_nbr[a1, s1], L_own[a2, s2], L_nbr[a2, s2])

        st = SparseADMMState(theta, K, Z_own, Z_nbr, L_own, L_nbr)
        if tel:
            with spans.phase("record"):
                stale, updates = tstate
                stale = tmetrics.staleness_step(stale, got, upd, n)
                updates = updates + jnp.sum(got)
                tstate = (stale, updates)
        return (st, pay, *tstate), None

    def outer(carry, xs):
        carry, _ = jax.lax.scan(round_fn, carry, xs)
        st = carry[0]
        if tel:
            with spans.phase("record"):
                live = jnp.arange(k)[None, :] < deg_count[:, None]
                if primal is not None and primal.needs_data:
                    loss_vec = primal.batch_local_loss(st.theta, *xym)
                    obj = tmetrics.cl_local_objective_from_loss(
                        st.theta, st.K, nbr_w, live, D, loss_vec, mu)
                else:
                    (sxx,) = tel_args
                    obj = tmetrics.cl_local_objective(
                        st.theta, st.K, nbr_w, live, D, m_counts, sx, sxx,
                        mu)
                stale, updates = carry[2:]
                return carry, (st.theta, obj, stale, updates)
        return carry, st.theta

    with spans.phase("init"):
        # the warm start publishes state0: round 0's stale and fresh rows
        # and round 1's stale duals all read it
        a0, s0 = (c[0] for c in cells)
        a1, s1 = (c[0, 0] for c in ahead[:2])
        pay0 = (state0.theta[a0], state0.K[a0, s0], state0.L_own[a0, s0],
                state0.L_nbr[a0, s0], state0.L_own[a0, s0],
                state0.L_nbr[a0, s0], state0.L_own[a1, s1],
                state0.L_nbr[a1, s1])
        carry0 = (state0, pay0)
        if tel:
            carry0 = carry0 + (jnp.zeros((n,), jnp.int32), jnp.int32(0))
    carry, hist = jax.lax.scan(outer, carry0, (ev, ahead))
    return carry[0], hist


def run_cl_scenario(topo: SparseTopology, data: AgentData, mu: float,
                    rho: float, conditions: NetworkConditions, rounds: int,
                    batch: int, seed: int = 0, record_every: int = 10,
                    theta_sol=None, state: Optional[SparseADMMState] = None,
                    stream: Optional[EventStream] = None,
                    backend: Optional[ReproBackend] = None,
                    telemetry: Optional[TelemetryConfig] = None,
                    primal=None) -> CLSimTrace:
    """Asynchronous CL-ADMM (paper §4.2) under a fault scenario.

    The same batched-event substrate as ``run_mp_scenario``: the fault
    process is materialized once (``scheduler.precompute_event_stream``,
    identical RNG schedule) and replayed, B wake-ups per round, with drops,
    staleness, stragglers, churn and partition windows all honored.  Pass
    ``stream`` to replay an externally drawn schedule (e.g. the exact
    engine's tick sequence) — its shape then fixes ``rounds`` x ``batch``.

    With all-default ``NetworkConditions`` every handshake completes and a
    round is exactly ``batch`` ticks of ``sparse_async_admm`` (same primal,
    same edge update, collisions coalesced deterministically).  The horizon
    follows the shared recording policy (``core.sparse.record_chunks``).

    ``primal`` selects the primal-phase solver (``core.primal``): ``None``
    / ``ExactQuadraticPrimal()`` is the closed-form quadratic solve;
    ``InexactPrimal(...)`` runs B AdamW steps on the local Lagrangian,
    supporting nonlinear losses and flattened neural agents — then
    ``theta_sol`` must carry the (n, p) flat parameter rows (e.g. from
    ``core.primal.solitary_adamw``), which fix the slot-row width p
    independently of the feature dimension of ``data.x``.
    """
    n = topo.n
    record_every, n_rec = record_chunks(rounds, record_every)
    total_rounds = n_rec * record_every
    if state is None and theta_sol is None:
        raise ValueError("need theta_sol (warm start) or explicit state")
    if stream is not None and stream.i.shape[0] != total_rounds:
        raise ValueError(
            f"stream covers {stream.i.shape[0]} rounds but the clamped "
            f"horizon is {total_rounds}")
    needs_data = primal is not None and primal.needs_data
    tel = telemetry_on(telemetry)
    with spans.host("prepare") as span:
        halves = topo.partition_halves() if stream is None else None
        span.set_metadata(bytes=spans.upload_bytes(
            topo.tables, state, halves, data.x, data.mask,
            data.y if needs_data else None,
            theta_sol if state is None else None))
        tabs = topo.device_tables()
        if state is None:
            # warm start built inside the jitted scan (see
            # _cl_scenario_scan)
            state = (tabs.nbr_idx,
                     jnp.asarray(theta_sol, jnp.float32).reshape(n, -1))
        if stream is None:
            stream = precompute_event_stream(
                tabs, jnp.asarray(halves), conditions, batch, seed,
                total_rounds)
        else:
            batch = int(stream.i.shape[1])

        D = jnp.asarray(tabs.deg_w, jnp.float32)
        mask = jnp.asarray(data.mask, jnp.float32)
        x = jnp.asarray(data.x, jnp.float32)
        m_counts = jnp.sum(mask, axis=1)
        sx = jnp.sum(x * mask[:, :, None], axis=1)
        xym = (x, jnp.asarray(data.y, jnp.float32), mask) if needs_data \
            else ()
        tel_args = ()
        if tel and not needs_data:
            # the quadratic objective's sufficient statistic; data-hungry
            # solvers evaluate their loss directly from xym instead
            sxx = jnp.sum(mask * jnp.sum(x * x, axis=-1), axis=1)
            tel_args = (sxx,)

        ev = _reshape_stream(stream, n_rec, record_every)
    p = (state.theta if isinstance(state, SparseADMMState)
         else state[1]).shape[1]
    # the scan carries 8 payload row arrays of (2B, p) f32 (_cl_scenario_scan)
    with spans.host("scan", payload_rows=2 * batch,
                    payload_carry_bytes=8 * 2 * batch * p * 4):
        st, hist = _cl_scenario_scan(
            tabs.nbr_w, tabs.deg_count, D, m_counts, sx, state, ev,
            tel_args, xym, mu=mu, rho=rho, backend=backend, tel=tel,
            primal=primal)
    delivered, dropped, invalid, active_hist = _fetch_stream_totals(
        stream, n_rec, record_every)
    with spans.host("wait"):
        jax.block_until_ready((st, hist))
    with spans.host("fetch", bytes=spans.fetch_bytes(
            hist, (stream.cut, stream.dead) if tel else None)):
        frames = None
        if tel:
            hist, obj_h, stale_h, upd_h = hist
            frames = TelemetryFrames(
                rounds=(np.arange(n_rec) + 1) * record_every,
                objective=np.asarray(obj_h), staleness=np.asarray(stale_h),
                updates=np.asarray(upd_h, np.int64),
                **tmetrics.stream_chunk_totals(stream, n_rec, record_every))
        return CLSimTrace(theta_hist=np.asarray(hist),
                          active_hist=active_hist, delivered=delivered,
                          dropped=dropped, rounds=total_rounds,
                          events=total_rounds * batch, invalid=invalid,
                          final=st, telemetry=frames)


# ---------------------------------------------------------------------------
# Joint model + collaboration-graph learning (DESIGN.md §13)
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class JointSimTrace(SimTrace):
    """SimTrace plus the graph-learning outputs.

    final_w / final_live: (n, k) learned row-stochastic weights and the
        surviving-candidate mask (live == candidate mask when pruning is
        off);
    live_edges_hist: (n_records,) live directed-slot count per snapshot;
    suppressed: deliveries voided because the *receiver* had pruned the
        edge — a subset of ``delivered`` (the stream-level accounting
        invariant is unchanged).
    """

    final_w: Optional[np.ndarray] = None
    final_live: Optional[np.ndarray] = None
    live_edges_hist: Optional[np.ndarray] = None
    suppressed: int = 0


@partial(jax.jit, static_argnames=("alpha", "eta_graph", "lam", "graph_every",
                                   "prune_eps", "backend", "tel"))
def _joint_scenario_scan(w0, live0, theta0, K0, c, theta_sol, ev, ts, *,
                         alpha: float, eta_graph: float, lam: float,
                         graph_every: int, prune_eps, backend=None,
                         tel: bool = False):
    """Batched-event joint MP + graph-learning rounds over a precomputed
    event stream (Zantedeschi-style alternation; DESIGN.md §13).

    One round = the MP-gossip round of ``_scenario_scan`` — communication
    scatters, then the shared Eq. (6) update — except that the mixing
    weights are the *learned* row-stochastic ``w`` carried in the scan
    state rather than the frozen ``tabs.nbr_p``, and deliveries into a
    pruned receiver slot are voided (counted in ``suppressed``).  Every
    ``graph_every``-th round ends with the graph step
    (``core.graph_learning.reweight_rows`` + optional ``prune_rows``) over
    all agent rows.

    With ``eta_graph == 0`` (a static argument) the graph step is compiled
    out and ``w`` stays the initial ``nbr_p`` array: the round body is the
    identical arithmetic of ``_scenario_scan``, which is what makes the
    rate-0 trajectory bit-for-bit equal to ``run_mp_scenario``'s
    (tests/test_joint.py).
    """
    n = theta0.shape[0]
    prune = eta_graph > 0.0 and prune_eps is not None

    def round_fn(carry, inp):
        theta, K, theta_prev, w, live, suppressed, *tstate = carry
        theta_in = theta
        ev_t, t = inp

        # --- communication: all scatters land before any update reads
        with spans.phase("comm"):
            msg_i = jnp.where(ev_t.stale_ij[:, None], theta_prev[ev_t.i],
                              theta[ev_t.i])
            msg_j = jnp.where(ev_t.stale_ji[:, None], theta_prev[ev_t.j],
                              theta[ev_t.j])
            if prune:
                ok_ij = ev_t.deliver_ij & live[ev_t.j, ev_t.r]
                ok_ji = ev_t.deliver_ji & live[ev_t.i, ev_t.s]
                suppressed = suppressed \
                    + jnp.sum(ev_t.deliver_ij & ~ok_ij) \
                    + jnp.sum(ev_t.deliver_ji & ~ok_ji)
            else:
                ok_ij, ok_ji = ev_t.deliver_ij, ev_t.deliver_ji
            row_j = jnp.where(ok_ij, ev_t.j, n)
            row_i = jnp.where(ok_ji, ev_t.i, n)
            # scatter: last-write-wins — a repeated edge in one batch lands
            # the batch-order winner (same policy as the scenario engine
            # above)
            K = K.at[row_j, ev_t.r].set(msg_i, mode="drop")
            K = K.at[row_i, ev_t.s].set(msg_j, mode="drop")  # scatter: last-write-wins

        # --- update: Eq. (6) under the current learned weights
        with spans.phase("update"):
            upd = jnp.concatenate([ev_t.i, ev_t.j])
            got = jnp.concatenate([ok_ji, ok_ij])
            new = batched_model_update(w[upd], K[upd], c[upd],
                                       theta_sol[upd], alpha, backend)
            # scatter: idempotent — duplicate agents in upd recompute the
            # same row from the same post-communication K
            theta = theta.at[jnp.where(got, upd, n)].set(new, mode="drop")

        # --- graph step (compiled out entirely at rate 0)
        if eta_graph > 0.0:
            def do_graph(w, live):
                w2 = reweight_rows(theta, K, w, live, eta=eta_graph,
                                   lam=lam, backend=backend)
                if prune_eps is not None:
                    return prune_rows(w2, live, prune_eps)
                return w2, live

            with spans.phase("graph"):
                w, live = jax.lax.cond(
                    (t + 1) % graph_every == 0, do_graph,
                    lambda w, live: (w, live), w, live)

        if tel:
            with spans.phase("record"):
                stale, updates = tstate
                stale = tmetrics.staleness_step(stale, got, upd, n)
                updates = updates + jnp.sum(got)
                tstate = (stale, updates)
        return (theta, K, theta_in, w, live, suppressed, *tstate), None

    def outer(carry, inp):
        carry, _ = jax.lax.scan(round_fn, carry, inp)
        theta, K, _, w, live, suppressed, *tstate = carry
        with spans.phase("record"):
            edges = jnp.sum(live & (w > 0))
            if tel:
                # objective under the *learned* weights (pruned slots
                # weigh 0)
                obj = tmetrics.mp_local_objective(
                    theta, K, jnp.where(live, w, 0.0), c, theta_sol, alpha)
                stale, updates = tstate
                return carry, (theta, edges, obj, stale, updates,
                               suppressed)
        return carry, (theta, edges)

    carry0 = (theta0, K0, theta0, w0, live0, jnp.int32(0))
    if tel:
        carry0 = carry0 + (jnp.zeros((n,), jnp.int32), jnp.int32(0))
    return jax.lax.scan(outer, carry0, (ev, ts))


def run_joint_scenario(topo: SparseTopology, theta_sol, c, alpha: float,
                       conditions: NetworkConditions, rounds: int,
                       batch: int, seed: int = 0, record_every: int = 10, *,
                       eta_graph: float = 0.0, lam: float = 1.0,
                       graph_every: int = 1,
                       prune_eps: Optional[float] = None,
                       stream: Optional[EventStream] = None,
                       backend: Optional[ReproBackend] = None,
                       telemetry: Optional[TelemetryConfig] = None
                       ) -> JointSimTrace:
    """Joint MP gossip + collaboration-graph learning under a fault scenario
    (Zantedeschi et al. 2019 alternation on the DJAM-style asynchronous
    substrate; DESIGN.md §13).

    The same batched-event machinery as ``run_mp_scenario`` — identical RNG
    schedule, same ``NetworkConditions`` — with the topology itself now
    state: the *candidate* slot tables stay frozen (wake-ups remain uniform
    over the candidate neighbors, so the event stream is precomputed and
    replayable), while the mixing weights start at ``tabs.nbr_p`` and are
    re-estimated every ``graph_every`` rounds from local model distances
    (rate ``eta_graph``, sparsity temperature ``lam``).  ``prune_eps``
    permanently drops slots whose weight falls below it — the edge churn
    the partitioned engine's halo re-compaction keys off.

    ``eta_graph=0`` reproduces ``run_mp_scenario`` bit-for-bit on the
    identical event schedule (the graph step is compiled out).  The horizon
    follows the shared recording policy (``core.sparse.record_chunks``).
    """
    n = topo.n
    record_every, n_rec = record_chunks(rounds, record_every)
    total_rounds = n_rec * record_every
    if stream is not None and stream.i.shape[0] != total_rounds:
        raise ValueError(
            f"stream covers {stream.i.shape[0]} rounds but the clamped "
            f"horizon is {total_rounds}")
    tel = telemetry_on(telemetry)
    with spans.host("prepare") as span:
        halves = topo.partition_halves() if stream is None else None
        span.set_metadata(bytes=spans.upload_bytes(
            topo.tables, theta_sol, c, halves))
        tabs = topo.device_tables()
        theta_sol = jnp.asarray(theta_sol, jnp.float32).reshape(n, -1)
        c = jnp.asarray(c, jnp.float32)
        if stream is None:
            stream = precompute_event_stream(
                tabs, jnp.asarray(halves), conditions, batch, seed,
                total_rounds)
        else:
            batch = int(stream.i.shape[1])

        theta0, K0 = _mp_warm_start(tabs, theta_sol)
        w0 = tabs.nbr_p
        live0 = live_slots(tabs.deg_count, topo.k_max)
        ev = _reshape_stream(stream, n_rec, record_every)
        ts = jnp.arange(total_rounds, dtype=jnp.int32).reshape(
            n_rec, record_every)
    with spans.host("scan"):
        carry, outs = _joint_scenario_scan(
            w0, live0, theta0, K0, c, theta_sol, ev, ts, alpha=alpha,
            eta_graph=eta_graph, lam=lam, graph_every=graph_every,
            prune_eps=prune_eps, backend=backend, tel=tel)
    delivered, dropped, invalid, active_hist = _fetch_stream_totals(
        stream, n_rec, record_every)
    with spans.host("wait"):
        jax.block_until_ready((carry, outs))
    theta, K, _, w, live, suppressed = carry[:6]
    with spans.host("fetch", bytes=spans.fetch_bytes(
            outs, w, live, suppressed,
            (stream.cut, stream.dead) if tel else None)):
        frames = None
        if tel:
            hist, live_hist, obj_h, stale_h, upd_h, sup_h = outs
            frames = TelemetryFrames(
                rounds=(np.arange(n_rec) + 1) * record_every,
                objective=np.asarray(obj_h), staleness=np.asarray(stale_h),
                updates=np.asarray(upd_h, np.int64),
                suppressed=np.asarray(sup_h, np.int64),
                **tmetrics.stream_chunk_totals(stream, n_rec, record_every))
        else:
            hist, live_hist = outs
        return JointSimTrace(
            theta_hist=np.asarray(hist), active_hist=active_hist,
            delivered=delivered, dropped=dropped, rounds=total_rounds,
            events=total_rounds * batch, invalid=invalid,
            final_w=np.asarray(w), final_live=np.asarray(live),
            live_edges_hist=np.asarray(live_hist),
            suppressed=int(suppressed), telemetry=frames)
