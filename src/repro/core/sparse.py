"""Padded-neighbor (CSR-style) tables shared by the dense reference engines
and the sparse event-driven simulator (DESIGN.md §4).

The asynchronous algorithms (MP gossip §3.2, CL-ADMM §4.2) only ever touch an
agent's own row of state — its neighbors' models, its per-edge secondary
variables.  Everything they compute can therefore be expressed over a padded
neighbor layout:

    nbr_idx  (n, k_max) int32  — sorted neighbor ids; pad slots repeat the
                                 row's last real neighbor (never selected,
                                 weight exactly 0)
    rev_slot (n, k_max) int32  — rev_slot[i, s] = position of i in the
                                 neighbor list of j = nbr_idx[i, s]
    nbr_w    (n, k_max) f32    — raw edge weights W_ij (0 at pads)
    nbr_p    (n, k_max) f32    — stochastic weights P_ij = W_ij / D_ii
    slot_cdf (n, k_max) f32    — cumsum of the uniform neighbor-selection
                                 distribution pi_i over slots (flat at pads)
    deg_count (n,)      int32  — number of live slots per row

The dense reference engines in ``model_propagation`` / ``collaborative`` keep
their (n, n, p) state but route every inner aggregation, neighbor-selection
draw, and primal solve through the helpers below, gathered over these same
slot tables.  The sparse engines in ``repro.simulate`` apply the *identical*
jnp expressions to their (n, k_max, p) state.  Identical ops on identical
values make the two trajectories match bit-for-bit given the same RNG stream
— the property tested in tests/test_simulate.py.
"""

from __future__ import annotations

from typing import NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.dispatch import ReproBackend, resolve


class NeighborTables(NamedTuple):
    """Host-side (numpy) padded-neighbor tables; see module docstring."""

    nbr_idx: np.ndarray    # (n, k_max) int32
    rev_slot: np.ndarray   # (n, k_max) int32
    deg_count: np.ndarray  # (n,) int32
    nbr_w: np.ndarray      # (n, k_max) float32, raw W
    nbr_p: np.ndarray      # (n, k_max) float32, W / D
    slot_cdf: np.ndarray   # (n, k_max) float32
    deg_w: np.ndarray      # (n,) float64 weighted degree D_ii

    @property
    def n(self) -> int:
        """Number of agents (rows)."""
        return self.nbr_idx.shape[0]

    @property
    def k_max(self) -> int:
        """Padded slot count (max degree over agents)."""
        return self.nbr_idx.shape[1]

    def with_weights(self, nbr_w_new: np.ndarray) -> "NeighborTables":
        """New tables carrying updated per-slot weights (time-varying graphs).

        The *candidate* structure — ``nbr_idx``, ``rev_slot``, ``deg_count``
        and the uniform wake-up cdf ``slot_cdf`` (pi_i, paper §3.2) — is kept
        frozen: the joint graph-learning engines (DESIGN.md §13) only move
        the weights within a fixed candidate support, which is what keeps
        the event process precomputable and replayable.  ``nbr_w``,
        ``nbr_p`` and ``deg_w`` are recomputed from ``nbr_w_new`` (dead
        slots zeroed; zero-degree rows get an all-zero stochastic row).
        """
        live = np.arange(self.k_max)[None, :] < self.deg_count[:, None]
        w = np.where(live, np.asarray(nbr_w_new, np.float64), 0.0)
        deg_w = w.sum(axis=1)
        nbr_p = np.where(live, w / np.where(deg_w > 0, deg_w, 1.0)[:, None],
                         0.0)
        return self._replace(nbr_w=w.astype(np.float32),
                             nbr_p=nbr_p.astype(np.float32), deg_w=deg_w)


def tables_from_adjacency(nbr_lists: Sequence[np.ndarray],
                          weight_lists: Sequence[np.ndarray],
                          deg_w: Optional[np.ndarray] = None,
                          allow_isolated: bool = False) -> NeighborTables:
    """Build NeighborTables from per-agent sorted neighbor/weight lists.

    Never materializes an n x n matrix: O(n * k_max) memory throughout, so it
    is the constructor used by the large-topology generators as well as by
    ``padded_neighbor_tables`` (which extracts the lists from a dense Graph).

    ``deg_w`` overrides the weighted degrees — Graph-derived tables pass the
    dense ``W.sum(axis=1)`` so D_ii matches the reference engines bitwise.

    ``allow_isolated=True`` admits degree-0 agents (churned-out sensors,
    stragglers that never joined): their rows carry deg_count 0, all-zero
    weights and a flat slot cdf, and every event engine treats a wake-up of
    such an agent as a no-op (see ``sample_event`` / ``scheduler.draw_events``).
    """
    n = len(nbr_lists)
    deg_count = np.array([len(a) for a in nbr_lists], np.int32)
    if (deg_count == 0).any() and not allow_isolated:
        raise ValueError("every agent needs at least one neighbor")
    k_max = max(1, int(deg_count.max()))

    nbr_idx = np.zeros((n, k_max), np.int32)
    nbr_w = np.zeros((n, k_max), np.float32)
    for i, (nb, wt) in enumerate(zip(nbr_lists, weight_lists)):
        d = len(nb)
        if d == 0:
            continue                     # isolated: all-zero row
        nbr_idx[i, :d] = nb
        nbr_idx[i, d:] = nb[-1]          # pads duplicate the last neighbor
        nbr_w[i, :d] = wt

    if deg_w is None:
        deg_w = np.array([np.asarray(w, np.float64).sum()
                          for w in weight_lists])
    deg_w = np.asarray(deg_w, np.float64)
    live = np.arange(k_max)[None, :] < deg_count[:, None]
    nbr_p = np.where(live, nbr_w.astype(np.float64)
                     / np.where(deg_w > 0, deg_w, 1.0)[:, None],
                     0.0).astype(np.float32)

    # uniform neighbor-selection cdf over slots (pi_i, paper §3.2); float32
    # cumsum so both engines compare u against bit-identical thresholds
    probs = np.where(live,
                     (1.0 / np.maximum(deg_count, 1)[:, None])
                     .astype(np.float32),
                     np.float32(0.0)).astype(np.float32)
    slot_cdf = np.cumsum(probs, axis=1, dtype=np.float32)

    # rev_slot via one lexsort over the directed edge list: within each
    # destination block, the rank of (dst, src) is src's slot in dst's row
    src = np.repeat(np.arange(n, dtype=np.int64), deg_count)
    dst = np.concatenate([np.asarray(a, np.int64) for a in nbr_lists])
    slot = np.concatenate([np.arange(d, dtype=np.int64) for d in deg_count])
    order = np.lexsort((src, dst))
    block_start = np.concatenate([[0], np.cumsum(deg_count)[:-1]])
    rank = np.empty(len(src), np.int64)
    # scatter: unique targets (order is a permutation)
    rank[order] = np.arange(len(src)) - block_start[dst[order]]
    rev = np.zeros((n, k_max), np.int32)
    rev[src, slot] = rank  # scatter: unique targets
    for i in range(n):                   # pads copy the last real slot's rev
        rev[i, deg_count[i]:] = rev[i, deg_count[i] - 1]

    return NeighborTables(nbr_idx, rev, deg_count, nbr_w, nbr_p,
                          slot_cdf, deg_w)


def padded_neighbor_tables(graph, allow_isolated: bool = False
                           ) -> NeighborTables:
    """NeighborTables of a ``core.graph.Graph`` (small/medium n only).

    ``allow_isolated`` passes through to :func:`tables_from_adjacency`:
    graphs with zero-degree agents (e.g. a thresholded kernel graph that
    disconnected a point) are rejected by default, admitted as no-op rows
    when True.
    """
    W = np.asarray(graph.W)
    nbrs = [np.nonzero(W[i])[0] for i in range(W.shape[0])]
    wts = [W[i, nb] for i, nb in enumerate(nbrs)]
    return tables_from_adjacency(nbrs, wts, deg_w=W.sum(axis=1),
                                 allow_isolated=allow_isolated)


class DeviceTables(NamedTuple):
    """Device-resident mirror of NeighborTables (what jitted engines take)."""

    nbr_idx: jnp.ndarray
    rev_slot: jnp.ndarray
    deg_count: jnp.ndarray
    nbr_w: jnp.ndarray
    nbr_p: jnp.ndarray
    slot_cdf: jnp.ndarray
    deg_w: jnp.ndarray


def to_device(tables: NeighborTables, dtype=jnp.float32) -> DeviceTables:
    """Mirror host-side tables onto the default device (weights cast to
    ``dtype``)."""
    return DeviceTables(
        jnp.asarray(tables.nbr_idx), jnp.asarray(tables.rev_slot),
        jnp.asarray(tables.deg_count), jnp.asarray(tables.nbr_w, dtype),
        jnp.asarray(tables.nbr_p, dtype), jnp.asarray(tables.slot_cdf, dtype),
        jnp.asarray(tables.deg_w, dtype))


# ---------------------------------------------------------------------------
# Shared jnp building blocks (used verbatim by dense AND sparse engines)
# ---------------------------------------------------------------------------


def live_slots(deg_count, k_max: int):
    """(n, k_max) bool mask of live (non-pad) slots — ``slot < deg_count``.

    The expression every engine previously inlined; exposed so the joint
    graph-learning state (``w``, ``live``) initializes identically on the
    single-device and partitioned paths.
    """
    return jnp.arange(k_max)[None, :] < deg_count[:, None]


def sample_event(key, n: int, slot_cdf, deg_count):
    """One wake-up draw: (agent i, neighbor slot s) — paper §3.2 / §4.2.

    i is uniform over agents; the slot is drawn from pi_i by inverting the
    float32 slot cdf (clipped to the live range so pads are never selected).

    Degree-0 agents (``allow_isolated`` tables) have an all-zero cdf: the
    raw clamp ``min(s, deg - 1)`` would yield -1, which wraps via negative
    indexing into the last pad slot and fabricates a phantom edge.  The slot
    is therefore clamped to [0, max(deg - 1, 0)] and every consumer must
    treat an event with ``deg_count[i] == 0`` as a no-op (the engines
    redirect their scatters out of bounds, where they are dropped).
    """
    ki, kj = jax.random.split(key)
    i = jax.random.randint(ki, (), 0, n)
    u = jax.random.uniform(kj)
    s = jnp.searchsorted(slot_cdf[i], u, side="right").astype(jnp.int32)
    s = jnp.maximum(jnp.minimum(s, deg_count[i] - 1), 0)
    return i, s


def record_chunks(steps: int, record_every: int) -> tuple:
    """The repo-wide recording policy for chunked scan engines.

    Every engine that records one snapshot per ``record_every`` steps uses

        record_every, n_rec = record_chunks(steps, record_every)

    and runs exactly ``n_rec * record_every`` steps: ``record_every`` is
    clamped to ``[1, steps]`` and the horizon is floored to a whole number
    of chunks.  This guarantees the run is never silently empty
    (``steps < record_every`` previously yielded ``n_rec = 0`` — zero steps
    and an empty history) and never overruns the requested horizon
    (``max(1, steps // record_every)`` previously ran a full oversized
    chunk).  Non-divisible ``steps`` are floored; traces report the actual
    count.  ``steps < 1`` raises.
    """
    if steps < 1:
        raise ValueError(f"steps must be >= 1, got {steps}")
    record_every = max(1, min(int(record_every), int(steps)))
    return record_every, steps // record_every


def neighbor_aggregate(w_slots, theta_slots,
                       backend: Optional[ReproBackend] = None):
    """sum_s w[s] * theta[s]  over the k_max slot axis: (k,), (k, p) -> (p,).

    The single shared reduction both engines use — same shapes, same HLO,
    bit-identical result (pad slots contribute an exact 0.0 * value).
    Dispatched through ``kernels.dispatch`` ("neighbor_aggregate" op); both
    engines must pass the same ``backend`` to keep their trajectories
    bit-identical.
    """
    return resolve("neighbor_aggregate", backend)(w_slots, theta_slots)


def batched_model_update(nbr_p_rows, K_rows, c_rows, sol_rows, alpha,
                         backend: Optional[ReproBackend] = None):
    """Eq. (6) model update for a batch of agents' slot rows.

    nbr_p_rows: (B, k) stochastic weights; K_rows: (B, k, p) neighbor
    models; c_rows: (B,) confidences; sol_rows: (B, p) solitary models.
    Returns the (B, p) updated models

        theta_i = (alpha * sum_s P[i,s] K[i,s] + (1-alpha) c_i sol_i)
                  / (alpha + (1-alpha) c_i)

    This is THE per-shard step: the single-device scenario engine applies
    it to the rows it gathers from its flat (n * k, p) slot table, the
    partitioned engine (``simulate.partition``) to rows of each shard's
    local block, and the dense references reach the same reduction through
    ``neighbor_aggregate`` — all dispatched through ``kernels.dispatch``,
    so the trajectories agree bit-for-bit whichever layout ran them.
    """
    agg = jax.vmap(lambda w_, K_: neighbor_aggregate(w_, K_, backend))(
        nbr_p_rows, K_rows)
    abar = 1.0 - alpha
    return (alpha * agg + abar * c_rows[:, None] * sol_rows) \
        / (alpha + abar * c_rows)[:, None]


def personalized_predict(theta_rows, x_rows):
    """Batched decode step of the personalization service (DESIGN.md §16).

    theta_rows: (B, p) personalized model rows — each user's current
    gossip-smoothed Eq. (6) / Eq. (7) model, snapshotted from the
    :class:`repro.serve.store.AgentStateStore`; x_rows: (B, p) feature
    rows.  Returns the (B,) predictions ``<theta_u, x_u>`` — the linear
    / mean-estimation model family of paper §5, evaluated for many users
    in one fused op.  This is the arithmetic the serve engine jits: one
    tick serves a whole batch of users from their own parameter rows.
    """
    return jnp.sum(theta_rows * x_rows, axis=-1)


def quadratic_primal_core(w, live, z_own_s, z_nbr_s, l_own_s, l_nbr_s,
                          D_l, m_l, sx, mu, rho,
                          backend: Optional[ReproBackend] = None):
    """Exact argmin of the CL-ADMM local Lagrangian for the quadratic loss,
    over one agent's slot row (block elimination; paper §4.2 step 1).

    w: (k,) raw edge weights (0 at pads); live: (k,) bool;
    z/l slices: (k, p) agent-l secondary/dual rows; D_l, m_l scalars;
    sx: (p,) sum of l's local samples.  Returns (theta_l (p,), theta_js (k, p)).

    Dispatched through ``kernels.dispatch`` ("admm_primal" op); the math
    lives in ``kernels.ref.quadratic_primal`` (reference) with a fused XLA
    variant selected by default.
    """
    return resolve("admm_primal", backend)(
        w, live, z_own_s, z_nbr_s, l_own_s, l_nbr_s, D_l, m_l, sx, mu, rho)


def batched_admm_primal(w_rows, live_rows, z_own_rows, z_nbr_rows,
                        l_own_rows, l_nbr_rows, D_rows, m_rows, sx_rows,
                        mu, rho, backend: Optional[ReproBackend] = None):
    """Quadratic CL-ADMM primal (paper §4.2 step 1) for a batch of agents'
    slot rows: all leading axes are the batch B; returns (theta (B, p),
    theta_js (B, k, p)).

    This is the per-shard ADMM step the scenario engines share: the
    single-device ``run_cl_scenario`` applies it to rows of its global
    (n, k, p) state and the partitioned engine to rows of each shard's
    local block, so the trajectories agree bit-for-bit whichever layout ran
    them (same property as ``batched_model_update`` for MP).

    Dispatched through "admm_primal": row-wise implementations are vmapped;
    ``*_sharded`` implementations consume the stacked rows directly.

    This closed form is also one PrimalSolver among several: the engines'
    ``primal=None`` default calls it directly, and
    ``core.primal.ExactQuadraticPrimal`` delegates here verbatim, while
    ``core.primal.InexactPrimal`` replaces it with B AdamW steps for
    nonquadratic losses / nonlinear agent models (DESIGN.md §18).
    """
    if backend is None:
        from repro.kernels.dispatch import _env_default
        backend = ReproBackend(default=_env_default())
    fn = resolve("admm_primal", backend)
    if backend.impl_for("admm_primal").endswith("_sharded"):
        return fn(w_rows, live_rows, z_own_rows, z_nbr_rows, l_own_rows,
                  l_nbr_rows, D_rows, m_rows, sx_rows, mu, rho)
    return jax.vmap(lambda w, lv, zo, zn, lo, ln, D, m, sx: fn(
        w, lv, zo, zn, lo, ln, D, m, sx, mu, rho))(
        w_rows, live_rows, z_own_rows, z_nbr_rows, l_own_rows, l_nbr_rows,
        D_rows, m_rows, sx_rows)


def admm_edge_halfstep(theta_own, k_own, l_own, l_nbr,
                       theta_pay, k_pay, l_own_pay, l_nbr_pay, rho):
    """One endpoint's half of the CL-ADMM edge update (paper §4.2 steps 2-3).

    The waking edge's endpoints exchange payloads (the partner's post-primal
    self model, its copy-of-me slot, and its two dual slots) and each side
    updates its OWN (Z_own, Z_nbr, L_own, L_nbr) slots.  All arrays are
    (..., p) slices for a batch of event sides:

      theta_own — this side's post-primal self model
      k_own     — this side's copy of the partner (its K slot)
      l_own / l_nbr — this side's dual slots for the edge
      *_pay     — the same four quantities from the partner's payload

    Returns (z_own, z_nbr, l_own_new, l_nbr_new).  With a fresh (current)
    payload the two sides compute bit-identical Z values and the step is
    exactly ``simulate.engines._sparse_edge_zl``; under staleness or
    one-sided drops the mirrored copies may diverge — the asynchronous
    regime DJAM (arXiv:1803.09737) analyzes.
    """
    z_own = 0.5 * ((l_own + l_nbr_pay) / rho + theta_own + k_pay)
    z_nbr = 0.5 * ((l_own_pay + l_nbr) / rho + theta_pay + k_own)
    l_own_new = l_own + rho * (theta_own - z_own)
    l_nbr_new = l_nbr + rho * (k_own - z_nbr)
    return z_own, z_nbr, l_own_new, l_nbr_new
