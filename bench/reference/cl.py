"""Plain reference of asynchronous CL-ADMM under faults (paper §4, Eq. 7),
quadratic loss, exact primal.

Imports nothing of the system under test.  State, per agent i: the model
``theta[i]`` (p,); per directed edge ``e = (i, j)`` of the CSR graph: i's
copy of j's model ``K[e]``, the secondary variables ``Zo[e]`` (i's side)
and ``Zn[e]`` (j's side as i holds it), and their duals ``Lo[e]``,
``Ln[e]``.  A call starts warm (paper §4.2): theta and K are the solitary
means, Zo[e] = theta[i], Zn[e] = K[e], duals zero.  Edge weights are 1, so
D_i = deg(i).

One round, by the definition:

1. draw the round's events (``events.draw``);
2. primal: every endpoint that completed its handshake (its partner's
   payload was delivered) minimises its local Lagrangian in closed form
   from its round-start Z and L rows,

       b_s    = rho Zn_s - Ln_s,          w = 1,  denom = w + rho
       a      = D + 2 mu D m + rho deg - sum_s w^2 / denom
       rhs    = 2 mu D sum(x) + sum_s (rho Zo_s - Lo_s) + sum_s w b_s / denom
       theta  = rhs / a,                  theta_js = (w theta + b_s) / denom

   and writes theta and its K row;
3. the round's payload of every agent is its post-primal theta and K row
   and its round-start duals; a lagging sender's payload is last round's;
4. edge: every delivered direction updates the receiver's own slot of the
   edge from its post-primal values and the partner's payload,

       Zo = ((Lo + Ln') / rho + theta + K') / 2
       Zn = ((Lo' + Ln) / rho + theta' + K) / 2
       Lo += rho (theta - Zo),   Ln += rho (K - Zn)

   (primes: the partner's payload; K' is the partner's copy of the receiver);
5. the churn process toggles agents.

``dtype`` is the precision of the state and the arithmetic: float32 is the
reference, bfloat16 the control that must fail the comparison.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from . import events as E


@partial(jax.jit, static_argnames=("cond", "batch", "mu", "rho"),
         donate_argnums=(0,))
def _round(state, key, t, g, rate, D, m, sx, *, cond, batch, mu, rho):
    (theta, K, Zo, Zn, Lo, Ln, active), prev = state
    cond = dict(cond)
    n = theta.shape[0]
    dt = theta.dtype
    mu, rho = jnp.asarray(mu, dt), jnp.asarray(rho, dt)
    k_ev, k_churn = jax.random.split(key)
    ev = E.draw(k_ev, cond, g, active, rate, t, batch)
    upd = jnp.concatenate([ev.i, ev.j])
    got = jnp.concatenate([ev.deliver_ji, ev.deliver_ij])

    # primal, from round-start rows
    idx, live = g.pad_idx[upd], g.pad_live[upd][:, :, None]
    Du, deg = D[upd][:, None], g.deg[upd][:, None].astype(dt)
    b = rho * Zn[idx] - Ln[idx]
    denom = 1 + rho
    a = Du + 2 * mu * Du * m[upd][:, None] + rho * deg - deg / denom
    rhs = (2 * mu * Du * sx[upd]
           + jnp.sum(jnp.where(live, rho * Zo[idx] - Lo[idx], 0), axis=1)
           + jnp.sum(jnp.where(live, b / denom, 0), axis=1))
    th = rhs / a
    th_js = (th[:, None, :] + b) / denom
    row = jnp.where(got, upd, n)
    theta = theta.at[row].set(th, mode="drop")
    edge = jnp.where(got[:, None] & live[:, :, 0], idx, K.shape[0])
    K = K.at[edge].set(th_js, mode="drop")

    # edge phase: side e is agent u = upd[e] updating its slot of the edge
    # to partner o, whose copy of u sits at o's slot os
    own_s = jnp.concatenate([ev.s, ev.r])
    oth = jnp.concatenate([ev.j, ev.i])
    oth_s = jnp.concatenate([ev.r, ev.s])
    stale = jnp.concatenate([ev.stale_ji, ev.stale_ij])[:, None]
    e_own = g.indptr[upd] + own_s
    e_oth = g.indptr[oth] + oth_s
    p_th, p_K, p_Lo, p_Ln = prev
    th_pay = jnp.where(stale, p_th[oth], theta[oth])
    k_pay = jnp.where(stale, p_K[e_oth], K[e_oth])
    lo_pay = jnp.where(stale, p_Lo[e_oth], Lo[e_oth])
    ln_pay = jnp.where(stale, p_Ln[e_oth], Ln[e_oth])
    th_o, k_o, lo_o, ln_o = theta[upd], K[e_own], Lo[e_own], Ln[e_own]
    zo = ((lo_o + ln_pay) / rho + th_o + k_pay) / 2
    zn = ((lo_pay + ln_o) / rho + th_pay + k_o) / 2
    dst = jnp.where(got, e_own, K.shape[0])
    payload = (theta, K, Lo, Ln)
    Zo = Zo.at[dst].set(zo, mode="drop")
    Zn = Zn.at[dst].set(zn, mode="drop")
    Lo = Lo.at[dst].set(lo_o + rho * (th_o - zo), mode="drop")
    Ln = Ln.at[dst].set(ln_o + rho * (k_o - zn), mode="drop")
    active = E.churn(k_churn, cond, active)
    return ((theta, K, Zo, Zn, Lo, Ln, active), payload), E.counts(ev)


def run(graph: E.RefGraph, inputs: dict, config: dict, traffic,
        seed: int, dtype=jnp.float32):
    """Counters (delivered, dropped, invalid) and the (n_rec, n, p)
    snapshots of one call with ``seed``, as host arrays."""
    cond = traffic.conditions
    n = graph.deg.shape[0]
    x = jnp.asarray(inputs["x"], dtype)
    mask = jnp.asarray(inputs["mask"], dtype)
    m = jnp.sum(mask, axis=1)
    sx = jnp.sum(x * mask[:, :, None], axis=1)
    sol = sx / jnp.maximum(m, 1)[:, None]
    D = graph.deg.astype(dtype)
    rate, keys = E.round_keys(seed, cond, n, traffic.rounds)
    K = sol[graph.indices]
    src = jnp.repeat(jnp.arange(n), graph.deg, total_repeat_length=K.shape[0])
    zeros = jnp.zeros_like(K)
    state = ((sol, K, sol[src], jnp.copy(K), zeros, jnp.zeros_like(K),
              jnp.ones((n,), bool)),
             (jnp.copy(sol), jnp.copy(K), jnp.zeros_like(K),
              jnp.zeros_like(K)))
    totals = jnp.zeros(3, jnp.int32)
    hist = []
    for t in range(traffic.rounds):
        state, cnt = _round(state, keys[t], jnp.int32(t), graph, rate, D, m,
                            sx, cond=tuple(sorted(cond.items())),
                            batch=traffic.batch, mu=config["mu"],
                            rho=config["rho"])
        totals = totals + cnt
        if (t + 1) % traffic.record_every == 0:
            hist.append(np.asarray(state[0][0], np.float32))
    return np.asarray(totals, np.int64), np.stack(hist)
