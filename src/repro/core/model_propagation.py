"""Model Propagation (paper §3).

Three equivalent solvers for  Q_MP(Theta) =
    1/2 ( sum_{i<j} W_ij ||theta_i - theta_j||^2
          + mu sum_i D_ii c_i ||theta_i - theta_i^sol||^2 ):

* ``closed_form``   — Prop. 1:  Theta* = abar (I - abar(I-C) - a P)^{-1} C Theta_sol
* ``synchronous``   — fixed-point iteration Eq. (5)
* ``async_gossip``  — the paper's asynchronous gossip algorithm (§3.2),
                      simulated exactly: uniform agent wake-up, one random
                      neighbor, communication + update steps, full
                      Theta_tilde in R^{n x n x p} state (row i = agent i's
                      knowledge of everyone; only N_i u {i} entries are live).

Convergence of async_gossip in expectation to Theta* is Theorem 1; it is
validated in tests/test_model_propagation.py and exercised at scale in
benchmarks/bench_mp_comm.py.

The inner wake-up sampling and neighbor aggregation go through the shared
padded-neighbor helpers in ``core.sparse`` so the O(n k p) event-driven
engine in ``repro.simulate`` reproduces this reference bit-for-bit
(DESIGN.md §4, tests/test_simulate.py).
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.dispatch import ReproBackend, resolve

from .graph import Graph
from .sparse import (neighbor_aggregate, padded_neighbor_tables,
                     record_chunks, sample_event, to_device)


def mp_mix_operator(P_rows, c, alpha):
    """Eq. (5) as a "mix" op:  theta' = A_mix @ theta + b * theta_sol.

    A_mix = diag(alpha / (alpha + abar c)) P,  b = abar c / (alpha + abar c).
    ``P_rows`` may be the dense (n, n) stochastic matrix or the (n, k)
    padded-neighbor slot weights (row scaling is identical) — the single
    derivation shared by ``synchronous``, ``simulate.engines.sparse_sync_mp``
    and ``experiments.sweep``.
    """
    abar = 1.0 - alpha
    denom = alpha + abar * c
    A_mix = (alpha / denom)[:, None] * P_rows
    b = abar * c / denom
    return A_mix, b


def mp_objective(theta, theta_sol, W, c, mu):
    """Q_MP — used by tests to verify optimality of the closed form."""
    W = jnp.asarray(W)
    diff = theta[:, None, :] - theta[None, :, :]
    # sum_{i<j} W_ij ||.||^2 == 1/2 sum_{i,j} W_ij ||.||^2 for symmetric W,
    # and Q_MP carries an outer 1/2 -> 0.25 overall.
    smooth = 0.25 * jnp.sum(W * jnp.sum(diff * diff, axis=-1))
    D = jnp.sum(W, axis=1)
    anchor = 0.5 * mu * jnp.sum(D * c * jnp.sum((theta - theta_sol) ** 2, axis=-1))
    return smooth + anchor


def closed_form(graph: Graph, theta_sol, c, alpha: float) -> jnp.ndarray:
    """Prop. 1:  Theta* = abar (I - abar(I - C) - alpha P)^{-1} C Theta_sol."""
    n = graph.n
    ftype = jnp.float64 if jax.config.jax_enable_x64 else jnp.float32
    P = jnp.asarray(graph.P, ftype)
    theta_sol = jnp.asarray(theta_sol, ftype).reshape(n, -1)
    c = jnp.asarray(c, ftype)
    abar = 1.0 - alpha
    A = jnp.eye(n) - abar * (jnp.eye(n) - jnp.diag(c)) - alpha * P
    return abar * jnp.linalg.solve(A, c[:, None] * theta_sol)


def synchronous(graph: Graph, theta_sol, c, alpha: float, steps: int,
                theta0=None,
                backend: Optional[ReproBackend] = None) -> jnp.ndarray:
    """Fixed-point iteration Eq. (5); converges to Theta* for any init.

    Each iterate is one ``mix`` op — A_mix @ theta + b * theta_sol with
    A_mix = diag(alpha/(alpha+abar c)) P and b = abar c/(alpha+abar c) —
    resolved through ``kernels.dispatch`` (fused XLA by default,
    overridable per call via ``backend``).
    """
    n = graph.n
    P = jnp.asarray(graph.P, jnp.float32)
    theta_sol = jnp.asarray(theta_sol, jnp.float32).reshape(n, -1)
    c = jnp.asarray(c, jnp.float32)
    A_mix, b = mp_mix_operator(P, c, alpha)
    theta = theta_sol if theta0 is None else jnp.asarray(theta0, jnp.float32)
    mix = resolve("mix", backend)

    def step(theta, _):
        """One Eq. (5) iterate (the "mix" op)."""
        return mix(theta, theta_sol, A_mix, b), None

    theta, _ = jax.lax.scan(step, theta, None, length=steps)
    return theta


@dataclasses.dataclass
class AsyncTrace:
    """Result of the async gossip simulation.

    theta_hist: (n_records, n, p) — each agent's OWN model over time
    comms_hist: (n_records,)      — cumulative pairwise communications
    final_knowledge: (n, n, p)    — full Theta_tilde at the end
    """

    theta_hist: np.ndarray
    comms_hist: np.ndarray
    final_knowledge: np.ndarray


@partial(jax.jit, static_argnames=("steps", "record_every", "backend"))
def _async_scan(nbr_idx, nbr_p, slot_cdf, deg_count, theta_sol, c, alpha,
                key, steps, record_every, T0, backend=None):
    """Exact async gossip (§3.2) as a lax.scan.

    T is (n, n, p): T[i, j] = agent i's knowledge of agent j's model.
    One scan step = one clock tick = 2 pairwise communications (i->j, j->i).
    Neighbor selection and aggregation use the shared slot tables so the
    sparse engine (repro.simulate.engines) matches bit-for-bit.
    """
    n, _, p = T0.shape
    abar = 1.0 - alpha

    def local_update(T, l, tgt):
        """Update step Eq. (6) for agent l using its own knowledge row."""
        nbrs = T[l][nbr_idx[l]]                   # (k_max, p) gathered slots
        agg = neighbor_aggregate(nbr_p[l], nbrs, backend)  # (p,)
        new = (alpha * agg + abar * c[l] * theta_sol[l]) / (alpha + abar * c[l])
        # scatter: unique target — single scalar (tgt, l) cell
        return T.at[tgt, l].set(new, mode="drop")

    def step(carry, key):
        """One wake-up tick (§3.2): exchange self-models, update both
        endpoints via Eq. (6)."""
        T = carry
        i, s = sample_event(key, n, slot_cdf, deg_count)
        # degree-0 waker -> no-op (same masking as the sparse engines):
        # out-of-bounds scatter targets are dropped
        valid = deg_count[i] > 0
        j = nbr_idx[i, s]
        ti = jnp.where(valid, i, n)
        tj = jnp.where(valid, j, n)
        # communication step: exchange current self-models
        T = T.at[ti, j].set(T[j, j], mode="drop")  # scatter: unique target
        T = T.at[tj, i].set(T[i, i], mode="drop")  # scatter: unique target
        # update step for both endpoints
        T = local_update(T, i, ti)
        T = local_update(T, j, tj)
        return T, T[jnp.arange(n), jnp.arange(n)] if record_every == 1 else None

    if record_every == 1:
        keys = jax.random.split(key, steps)
        T, hist = jax.lax.scan(step, T0, keys)
        return T, hist

    # repro-lint: disable=RPL007  callers normalize via core.sparse.record_chunks
    n_rec = steps // record_every

    def outer(T, key):
        """One record chunk; emits a model snapshot."""
        keys = jax.random.split(key, record_every)
        T, _ = jax.lax.scan(lambda c, k: (step(c, k)[0], None), T, keys)
        return T, T[jnp.arange(n), jnp.arange(n)]

    keys = jax.random.split(key, n_rec)
    T, hist = jax.lax.scan(outer, T0, keys)
    return T, hist


def async_gossip(graph: Graph, theta_sol, c, alpha: float, steps: int,
                 seed: int = 0, record_every: int = 100,
                 theta0=None,
                 backend: Optional[ReproBackend] = None) -> AsyncTrace:
    """Run the asynchronous gossip MP algorithm (paper §3.2).

    ``steps`` clock ticks; each tick = 2 pairwise communications.
    Neighbor selection pi_i is uniform over N_i (as in the paper's §5).
    """
    n = graph.n
    theta_sol = jnp.asarray(theta_sol, jnp.float32).reshape(n, -1)
    p = theta_sol.shape[1]
    tabs = to_device(padded_neighbor_tables(graph))
    c = jnp.asarray(c, jnp.float32)

    if theta0 is None:
        # warm start with solitary models everywhere the agent has knowledge
        T0 = jnp.where(((graph.W > 0) | np.eye(n, dtype=bool))[:, :, None],
                       jnp.broadcast_to(theta_sol[None], (n, n, p)), 0.0)
        T0 = jnp.asarray(T0, jnp.float32)
    else:
        T0 = jnp.asarray(theta0, jnp.float32)

    key = jax.random.PRNGKey(seed)
    # shared recording policy (core.sparse.record_chunks): horizon floored
    # to a whole number of record chunks, never silently zero steps
    record_every, n_rec = record_chunks(steps, record_every)
    T, hist = _async_scan(tabs.nbr_idx, tabs.nbr_p, tabs.slot_cdf,
                          tabs.deg_count, theta_sol, c, alpha, key,
                          n_rec * record_every, record_every, T0, backend)
    comms = 2 * record_every * (np.arange(hist.shape[0]) + 1)
    return AsyncTrace(np.asarray(hist), comms, np.asarray(T))


def label_propagation(graph: Graph, labels, alpha: float) -> jnp.ndarray:
    """Zhou et al. (2004) — the C = I special case (paper §3.1 remark)."""
    n = graph.n
    return closed_form(graph, labels, np.ones(n), alpha)
