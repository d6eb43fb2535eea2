"""Plain reference of model propagation under faults (paper §3, Eq. 6).

Imports nothing of the system under test.  State is each agent's model
``theta`` (n, p) and, per directed edge ``e = (i, j)`` of the CSR graph,
agent i's copy of j's model ``K[e]`` (2E, p).  A call starts warm
(paper §3.2): every model and every copy is the solitary model.

One round, by the definition:

1. draw the round's events (``events.draw``);
2. every delivered direction writes the sender's model into the receiver's
   copy of it: the model at the start of this round, or at the start of the
   previous round when the sender lags;
3. every endpoint that received a message (and is active) recomputes

       theta_i = (alpha * mean_{j in N(i)} K[i, j] + (1 - alpha) c_i sol_i)
                 / (alpha + (1 - alpha) c_i)

   from its copies after all of this round's writes (Eq. 6 with uniform
   weights 1 / deg);
4. the churn process toggles agents.

A snapshot of ``theta`` is taken every ``record_every`` rounds.  ``dtype``
is the precision of the state and the arithmetic: float32 is the
reference, bfloat16 the control that must fail the comparison.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from . import events as E


@partial(jax.jit, static_argnames=("cond", "batch", "alpha"),
         donate_argnums=(0,))
def _round(state, key, t, g, rate, sol, c, *, cond, batch, alpha):
    theta, theta_prev, K, active = state
    cond = dict(cond)
    n = theta.shape[0]
    k_ev, k_churn = jax.random.split(key)
    ev = E.draw(k_ev, cond, g, active, rate, t, batch)
    msg_i = jnp.where(ev.stale_ij[:, None], theta_prev[ev.i], theta[ev.i])
    msg_j = jnp.where(ev.stale_ji[:, None], theta_prev[ev.j], theta[ev.j])
    oob = K.shape[0]
    K = K.at[jnp.where(ev.deliver_ij, g.indptr[ev.j] + ev.r, oob)].set(
        msg_i, mode="drop")
    K = K.at[jnp.where(ev.deliver_ji, g.indptr[ev.i] + ev.s, oob)].set(
        msg_j, mode="drop")
    upd = jnp.concatenate([ev.i, ev.j])
    got = jnp.concatenate([ev.deliver_ji, ev.deliver_ij]) & active[upd]
    rows = jnp.where(g.pad_live[upd][:, :, None], K[g.pad_idx[upd]], 0)
    mean = jnp.sum(rows, axis=1) / g.deg[upd][:, None].astype(theta.dtype)
    a, cu = jnp.asarray(alpha, theta.dtype), c[upd][:, None]
    new = (a * mean + (1 - a) * cu * sol[upd]) / (a + (1 - a) * cu)
    theta_next = theta.at[jnp.where(got, upd, n)].set(new, mode="drop")
    active = E.churn(k_churn, cond, active)
    return (theta_next, theta, K, active), E.counts(ev)


def run(graph: E.RefGraph, inputs: dict, config: dict, traffic,
        seed: int, dtype=jnp.float32):
    """Counters (delivered, dropped, invalid) and the (n_rec, n, p)
    snapshots of one call with ``seed``, as host arrays."""
    cond = traffic.conditions
    n = graph.deg.shape[0]
    sol = jnp.asarray(inputs["theta_sol"], dtype)
    c = jnp.asarray(inputs["c"], dtype)
    rate, keys = E.round_keys(seed, cond, n, traffic.rounds)
    state = (jnp.copy(sol), jnp.copy(sol), sol[graph.indices],
             jnp.ones((n,), bool))
    totals = jnp.zeros(3, jnp.int32)
    hist = []
    for t in range(traffic.rounds):
        state, cnt = _round(state, keys[t], jnp.int32(t), graph, rate, sol,
                            c, cond=tuple(sorted(cond.items())),
                            batch=traffic.batch, alpha=config["alpha"])
        totals = totals + cnt
        if (t + 1) % traffic.record_every == 0:
            hist.append(np.asarray(state[0], np.float32))
    return np.asarray(totals, np.int64), np.stack(hist)
