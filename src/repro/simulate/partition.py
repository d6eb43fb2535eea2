"""Graph-partitioned multi-device network simulation (DESIGN.md §11).

Shards the agent graph into P blocks, gives each shard padded local agent
state plus a *halo* buffer of remote-neighbor models, and runs the
event-driven MP-gossip engine under ``shard_map`` over a 1-D agent mesh
(``launch.sim_mesh``), exchanging halos between event batches.

Layout per shard (m = padded local agents, H = padded halo size)::

      theta_loc (m, p)   K_loc (m, k, p)   nbr_p_loc (m, k)  c/sol_loc
      ext = [ theta_loc | theta_halo (H, p) | 0-row ]   # message source

    fetch[q][agent] -> row of ext   (m + H = the zero row = "not here")

Between event batches each shard publishes its *boundary* rows (local
agents with a cross-shard edge, padded to B) and pulls its halo from the
gathered boundary buffers — ``all_gather`` by default, or a P-1-step
``ppermute`` ring (``exchange="ring"``).

Three properties make the sharded trajectory match the single-device
engine (``simulate.engines.run_mp_scenario``) bit-for-bit:

* the event stream is *precomputed* with the identical RNG schedule
  (``scheduler.precompute_event_stream``) and replayed by every shard —
  the fault process never reads model state, so this is exact;
* within a round, messages read round-start models; the halo refreshed at
  the top of each round IS the round-start snapshot of remote models (the
  previous round's halo serves the one-round-stale payloads);
* the per-agent update is the shared ``core.sparse.batched_model_update``
  applied to the receiver's own slot row — identical arithmetic whether
  the row lives in the global (n, k, p) state or a shard's local block.

The only approximation is the static per-shard update buffer: each round a
shard compacts its local delivery endpoints into ``local_batch`` slots
(default: mean + 8 sigma of the binomial receiver count, so overflow is
~never observed; overflowing events are counted in the trace and sized up
via ``local_batch`` if parity to the reference run is required).
"""

from __future__ import annotations

import dataclasses
import math
from collections import deque
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.core.graph_learning import prune_rows, reweight_rows
from repro.core.sparse import (admm_edge_halfstep, batched_admm_primal,
                               batched_model_update, live_slots,
                               record_chunks)
from repro.launch.sim_mesh import (AGENT_AXIS, HaloCodec, agent_sharding,
                                   halo_exchange_fn, halo_payload_bytes,
                                   make_sim_mesh, mesh_shards,
                                   resolve_halo_codec)
from repro.telemetry import metrics as tmetrics
from repro.telemetry.config import TelemetryConfig, telemetry_on
from repro.telemetry.frames import TelemetryFrames
from .engines import SimTrace, _reshape_stream, warm_admm_from_copies
from .scheduler import (EventStream, NetworkConditions,
                        precompute_event_stream, stream_totals)
from .topology import SparseTopology


# ---------------------------------------------------------------------------
# Greedy edge-cut partitioner (linear deterministic greedy over a BFS order)
# ---------------------------------------------------------------------------


def _bfs_order(topo: SparseTopology, seed: int) -> np.ndarray:
    """Deterministic BFS visit order; the seed picks each component's root."""
    tabs = topo.tables
    n = topo.n
    rng = np.random.default_rng(seed)
    seen = np.zeros(n, bool)
    order = np.empty(n, np.int64)
    pos = 0
    start = int(rng.integers(n))
    for root in range(n):
        root = (root + start) % n
        if seen[root]:
            continue
        seen[root] = True
        q = deque([root])
        while q:
            v = q.popleft()
            order[pos] = v
            pos += 1
            for u in tabs.nbr_idx[v, :tabs.deg_count[v]]:
                if not seen[u]:
                    seen[u] = True
                    q.append(int(u))
    return order


def greedy_partition(topo: SparseTopology, n_shards: int, seed: int = 0,
                     refine_passes: int = 4) -> np.ndarray:
    """Greedy edge-cut assignment of agents to ``n_shards`` balanced shards.

    Linear deterministic greedy (Stanton & Kleinberg): visit agents in BFS
    order and put each on the shard holding most of its already-placed
    neighbors, discounted by shard fullness and hard-capped at
    ceil(n / P) agents; then ``refine_passes`` local passes move each agent
    to its majority-neighbor shard when balance allows (never increases the
    cut).  O(E) per pass; deterministic for a fixed seed (the seed only
    picks BFS roots).  Returns the (n,) int32 shard id per agent.
    """
    n = topo.n
    if n_shards <= 1:
        return np.zeros(n, np.int32)
    tabs = topo.tables
    cap = math.ceil(n / n_shards)
    assign = np.full(n, -1, np.int32)
    sizes = np.zeros(n_shards, np.int64)
    order = _bfs_order(topo, seed)
    for v in order:
        nbrs = tabs.nbr_idx[v, :tabs.deg_count[v]]
        placed = assign[nbrs]
        cnt = np.bincount(placed[placed >= 0], minlength=n_shards)
        open_ = sizes < cap
        if cnt.max(initial=0) > 0:
            score = np.where(open_, cnt * (1.0 - sizes / cap), -1.0)
        else:                       # no placed neighbor: least-loaded shard
            score = np.where(open_, -sizes.astype(np.float64), -np.inf)
        s = int(np.argmax(score))
        assign[v] = s  # scatter: unique target (scalar vertex id)
        sizes[s] += 1  # scatter: unique target (scalar shard id)
    # refinement tolerates ~6% imbalance so moves stay possible when every
    # shard sits exactly at cap (the LDG pass always ends there)
    refine_cap = cap + max(1, cap // 16)
    for _ in range(refine_passes):
        moved = False
        for v in order:
            nbrs = tabs.nbr_idx[v, :tabs.deg_count[v]]
            cnt = np.bincount(assign[nbrs], minlength=n_shards)
            cur = assign[v]
            t = int(np.argmax(cnt))
            if t != cur and cnt[t] > cnt[cur] and sizes[t] < refine_cap:
                assign[v] = t  # scatter: unique target (scalar vertex id)
                sizes[t] += 1  # scatter: unique target (scalar shard id)
                sizes[cur] -= 1  # scatter: unique target (scalar shard id)
                moved = True
        if not moved:
            break
    return assign


def block_partition(topo: SparseTopology, n_shards: int) -> np.ndarray:
    """Contiguous-id blocks — the trivial baseline the greedy cut beats."""
    m = math.ceil(topo.n / max(1, n_shards))
    return (np.arange(topo.n) // m).astype(np.int32)


def _directed_edges(tabs, live=None):
    """Directed (receiver, sender) pairs of the candidate slot tables.

    src = the row owner (the agent whose slot it is — the *receiver* of
    messages on that slot), dst = the slot's neighbor (the sender).  An
    optional (n, k_max) bool ``live`` mask restricts to surviving slots
    (joint graph learning prunes slots; DESIGN.md §13).
    """
    cand = np.arange(tabs.k_max)[None, :] < tabs.deg_count[:, None]
    if live is not None:
        cand = cand & np.asarray(live, bool)
    rows, slots = np.nonzero(cand)
    return rows.astype(np.int64), tabs.nbr_idx[rows, slots].astype(np.int64)


def edge_cut(topo: SparseTopology, assignment: np.ndarray) -> int:
    """Number of undirected edges crossing shard boundaries."""
    src, dst = _directed_edges(topo.tables)
    a = np.asarray(assignment)
    return int((a[src] != a[dst]).sum()) // 2


# ---------------------------------------------------------------------------
# Partition layout: local blocks, boundary buffers, halo fetch tables
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class GraphPartition:
    """Host-side shard/halo layout of a topology (see module docstring).

    Shapes: owner/local_pos/perm_slot (n,); local_ids (P, m) with -1 pads;
    bnd_pos (P, B); halo_src_shard/halo_src_pos (P, H); fetch (P, n).
    ``fetch[q, a]`` is agent a's row in shard q's ext buffer: < m if local,
    m..m+H-1 if in q's halo, m+H (the zero row) otherwise.
    """

    n: int
    n_shards: int
    shard_size: int                 # m
    owner: np.ndarray
    local_pos: np.ndarray
    perm_slot: np.ndarray           # owner * m + local_pos
    local_ids: np.ndarray
    bnd_pos: np.ndarray
    halo_src_shard: np.ndarray
    halo_src_pos: np.ndarray
    fetch: np.ndarray
    edge_cut: int

    @property
    def halo_size(self) -> int:     # H (max over shards, 0 if no cut)
        """Per-shard halo slot count H (max over shards; 0 if no cut)."""
        return self.halo_src_shard.shape[1]

    @property
    def boundary_size(self) -> int:  # B
        """Per-shard boundary slot count B (rows other shards read)."""
        return self.bnd_pos.shape[1]

    @classmethod
    def build(cls, topo: SparseTopology, assignment: np.ndarray,
              n_shards: Optional[int] = None,
              live: Optional[np.ndarray] = None) -> "GraphPartition":
        """Shard/halo layout of ``topo`` under ``assignment``.

        ``live`` (optional, (n, k_max) bool) restricts the layout to the
        surviving directed slots of a joint graph-learning run: the halo
        of a shard then holds only the remote *senders* some local live
        slot still reads, and the boundary only the local agents some
        remote live slot still needs — the halo re-compaction the joint
        sharded engine performs when enough cross edges have been pruned
        (DESIGN.md §13).  The local block layout (owner / local_pos /
        perm_slot) depends only on ``assignment``, so re-compacted layouts
        are drop-in replacements for each other's sharded state.
        """
        tabs = topo.tables
        n = topo.n
        owner = np.asarray(assignment, np.int32)
        P_ = int(n_shards if n_shards is not None else owner.max() + 1)
        sizes = np.bincount(owner, minlength=P_)
        m = max(1, int(sizes.max()))

        by_shard = np.argsort(owner, kind="stable")      # id-sorted per shard
        local_pos = np.empty(n, np.int32)
        starts = np.concatenate([[0], np.cumsum(sizes)[:-1]])
        local_pos[by_shard] = (np.arange(n) - starts[owner[by_shard]]) \
            .astype(np.int32)  # scatter: unique targets (by_shard is a permutation)
        local_ids = np.full((P_, m), -1, np.int32)
        # scatter: unique targets ((owner, local_pos) pairs are distinct)
        local_ids[owner, local_pos] = np.arange(n, dtype=np.int32)
        perm_slot = owner.astype(np.int64) * m + local_pos

        src, dst = _directed_edges(tabs, live)
        cross = owner[src] != owner[dst]
        cut = int(cross.sum()) // 2

        # boundary: local agents some remote live slot reads (the *senders*
        # published each round), id-sorted per shard.  For the symmetric
        # live=None candidate tables this is exactly "local agents with any
        # cross edge".
        is_bnd = np.zeros(n, bool)
        is_bnd[dst[cross]] = True  # scatter: idempotent (every value is True)
        bnd_lists = [np.where(is_bnd & (owner == q))[0] for q in range(P_)]
        B = max((len(b) for b in bnd_lists), default=0)
        bnd_pos = np.zeros((P_, B), np.int32)
        bnd_rank = np.zeros(n, np.int64)
        for q, lst in enumerate(bnd_lists):
            bnd_pos[q, :len(lst)] = local_pos[lst]
            bnd_rank[lst] = np.arange(len(lst))

        # halo of q: remote endpoints of q's cross edges, id-sorted
        halo_lists = [np.unique(dst[cross & (owner[src] == q)])
                      for q in range(P_)]
        H = max((len(h) for h in halo_lists), default=0)
        halo_src_shard = np.zeros((P_, H), np.int32)
        halo_src_pos = np.zeros((P_, H), np.int32)
        fetch = np.full((P_, n), m + H, np.int32)
        fetch[owner, np.arange(n)] = local_pos  # scatter: unique targets
        for q, hl in enumerate(halo_lists):
            halo_src_shard[q, :len(hl)] = owner[hl]
            halo_src_pos[q, :len(hl)] = bnd_rank[hl]
            # scatter: unique targets (hl lists distinct halo agents)
            fetch[q, hl] = m + np.arange(len(hl), dtype=np.int32)

        return cls(n=n, n_shards=P_, shard_size=m, owner=owner,
                   local_pos=local_pos, perm_slot=perm_slot,
                   local_ids=local_ids, bnd_pos=bnd_pos,
                   halo_src_shard=halo_src_shard, halo_src_pos=halo_src_pos,
                   fetch=fetch, edge_cut=cut)

    def shard_rows(self, x: np.ndarray) -> np.ndarray:
        """Permute per-agent rows (n, ...) into the stacked padded layout
        (P * m, ...); pad rows are zero."""
        x = np.asarray(x)
        ids = self.local_ids.reshape(-1)
        out = x[np.maximum(ids, 0)]
        out[ids < 0] = 0  # scatter: unique targets (boolean mask)
        return out

    def unshard_rows(self, y):
        """Inverse of :meth:`shard_rows` along the last-but-(ndim-1) axis:
        (..., P * m, ...) indexed back to original agent order (..., n, ...).
        Works on the leading-agent axis right after any batch dims."""
        return np.asarray(y)[..., self.perm_slot, :]


# ---------------------------------------------------------------------------
# Sharded scenario engine
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class ShardedSimTrace(SimTrace):
    """SimTrace plus partition diagnostics.

    overflow: events that missed the static per-shard update buffer (0 =>
    the trajectory is exactly the single-device one).
    """

    n_shards: int = 1
    edge_cut: int = 0
    halo_size: int = 0
    local_batch: int = 0
    overflow: int = 0


def _binomial_cap(trials: int, n_shards: int, cap: int) -> int:
    """mean + 8 sigma of Binomial(trials, 1/P), clamped to the lossless
    capacity ``cap`` — at 8 sigma overflow is ~never observed, and any
    occurrence is counted in the trace."""
    if n_shards <= 1:
        return cap
    q = 1.0 / n_shards
    mean = trials * q
    std = math.sqrt(trials * q * (1.0 - q))
    return int(min(cap, math.ceil(mean + 8.0 * std + 16)))


def default_local_batch(batch: int, n_shards: int) -> int:
    """Static per-shard update capacity (each of 2B endpoints lands on a
    given shard w.p. ~1/P; 2B = lossless whatever the draw)."""
    return _binomial_cap(2 * batch, n_shards, 2 * batch)


def default_local_events(batch: int, n_shards: int) -> int:
    """Static per-shard event capacity (an event is relevant to a shard
    when it owns either endpoint, w.p. <= 2/P)."""
    return _binomial_cap(2 * batch, n_shards, batch)


def _sharded_setup(topo, n_shards, mesh, assignment, partition_seed):
    """Shared preamble of the three sharded runners: resolve the mesh,
    the shard assignment (greedy by default, validated when explicit)
    and the graph partition.  Returns ``(mesh, P_, assignment, part)``.
    """
    mesh = make_sim_mesh(n_shards) if mesh is None else mesh
    P_ = mesh_shards(mesh)
    if assignment is None:
        assignment = greedy_partition(topo, P_, seed=partition_seed)
    elif int(np.max(assignment)) >= P_:
        raise ValueError(
            f"assignment uses shard {int(np.max(assignment))} but the mesh "
            f"has only {P_} devices (start the process with "
            f"XLA_FLAGS=--xla_force_host_platform_device_count=<P> for "
            f"fake host devices)")
    part = GraphPartition.build(topo, assignment, P_)
    return mesh, P_, assignment, part


def _on_shards(mesh, x):
    """Place host rows in the stacked (P * m, ...) layout (or one row per
    shard) on the mesh, each device receiving only its own block — never
    the whole array on one device first."""
    return jax.device_put(np.asarray(x), agent_sharding(mesh))


def _partition_args(mesh, part: GraphPartition) -> dict:
    """The per-shard halo tables every sharded scan takes, on the mesh."""
    return dict(fetch=_on_shards(mesh, part.fetch),
                bnd_pos=_on_shards(mesh, part.bnd_pos),
                halo_src_shard=_on_shards(mesh, part.halo_src_shard),
                halo_src_pos=_on_shards(mesh, part.halo_src_pos))


def _local_capacities(batch: int, P_: int, local_batch) -> tuple:
    """Per-shard static (event, update) capacities ``(E, U)`` — the
    8-sigma defaults, or the lossless explicit-capacity override."""
    if local_batch is None:
        E = default_local_events(batch, P_)
        U = default_local_batch(batch, P_)
    else:                      # explicit capacity: lossless event selection
        E = batch
        U = max(1, min(local_batch, 2 * batch))
    return E, min(U, 2 * E)


def _scan_specs(P_spec, tree):
    return jax.tree_util.tree_map(lambda _: P_spec, tree)


def _sharded_frames(part: GraphPartition, stream, n_rec: int,
                    record_every: int, obj_h, stale_h, upd_h, overflow,
                    payload_row_bytes: int, halo_bytes=None,
                    suppressed=None) -> TelemetryFrames:
    """Reassemble per-shard telemetry blocks into canonical-order frames.

    obj_h / stale_h are (n_rec, P * m) gathered block outputs — indexed
    back to agent order via ``perm_slot`` (not ``unshard_rows``, which
    needs a trailing feature axis); upd_h is (n_rec, P) per-shard counters
    summed exactly here.  Delivery/drop-cause accounting reduces from the
    replayed stream (``metrics.stream_chunk_totals`` — the identical
    counts the single-device engines accumulate).  ``payload_row_bytes``
    sizes the per-boundary-row halo publish; ``halo_bytes`` overrides the
    static cumulative schedule (the joint driver recomputes it per
    segment as re-compaction shrinks the boundary).
    """
    rounds = (np.arange(n_rec, dtype=np.int64) + 1) * record_every
    if halo_bytes is None:
        halo_bytes = rounds * halo_payload_bytes(
            part.n_shards, part.boundary_size, payload_row_bytes,
            part.halo_size)
    return TelemetryFrames(
        rounds=rounds,
        objective=np.asarray(obj_h)[:, part.perm_slot],
        staleness=np.asarray(stale_h)[:, part.perm_slot],
        updates=np.asarray(upd_h, np.int64).sum(axis=1),
        halo_bytes=halo_bytes,
        overflow_per_shard=np.asarray(overflow),
        suppressed=suppressed,
        **tmetrics.stream_chunk_totals(stream, n_rec, record_every))


def _take_padded(x, sel, fill):
    """x[sel] where the out-of-range selector index len(x) reads ``fill``."""
    return jnp.concatenate([x, jnp.full((1,), fill, x.dtype)])[sel]


@partial(jax.jit,
         static_argnames=("mesh", "alpha", "m", "H", "E", "U", "n_rec",
                          "record_every", "exchange", "codec", "tel"))
def _sharded_scenario_scan(mesh, stream, theta0, K0, nbr_p, c, sol,
                           fetch, bnd_pos, halo_src_shard, halo_src_pos, *,
                           alpha: float, m: int, H: int, E: int, U: int,
                           n_rec: int, record_every: int, exchange: str,
                           codec: HaloCodec = HaloCodec("f32"),
                           tel: bool = False):
    """shard_map'd scan over rounds; every array argument before ``fetch``
    is either replicated (the event stream) or row-sharded (P * m leading
    axis); ``fetch``/``bnd_pos``/``halo_src_*`` carry one row per shard.

    ``tel`` (static) adds local-row staleness/update accumulators to each
    shard's carry and per-chunk (objective, staleness, updates) block
    outputs — the identical row-local expressions the single-device scan
    accumulates, applied to the shard's (m, ...) block, so the
    reassembled vectors are bit-for-bit the single-device ones whenever
    overflow is 0.  At the default False the traced program is exactly
    the pre-telemetry scan."""
    P_ = mesh_shards(mesh)
    batch = stream.i.shape[-1]

    def block_fn(ev, theta0_blk, K0_blk, nbr_p_blk, c_blk, sol_blk,
                 fetch_blk, bnd_blk, hsrc_blk, hpos_blk):
        fetch_q = fetch_blk[0]
        bnd = bnd_blk[0]
        hsrc, hpos = hsrc_blk[0], hpos_blk[0]
        # publish boundary rows, pull this shard's halo (round-start
        # snapshot of remote-neighbor models)
        exchange_halo = halo_exchange_fn(bnd, hsrc, hpos, H, P_, exchange,
                                         codec=codec)

        def round_fn(carry, ev_t):
            theta, K, ext_prev, overflow, *tstate = carry
            ext = exchange_halo(theta)

            # --- compact to the events touching this shard: everything
            # below (message gathers, slot scatters, updates) then runs at
            # O(E) ~ 2B/P instead of O(B) per shard
            rel = (fetch_q[ev_t.i] < m) | (fetch_q[ev_t.j] < m)
            sel = jnp.nonzero(rel, size=E, fill_value=batch)[0]
            i = _take_padded(ev_t.i, sel, 0)
            j = _take_padded(ev_t.j, sel, 0)
            s = _take_padded(ev_t.s, sel, 0)
            r = _take_padded(ev_t.r, sel, 0)
            d_ij = _take_padded(ev_t.deliver_ij, sel, False)
            d_ji = _take_padded(ev_t.deliver_ji, sel, False)
            st_ij = _take_padded(ev_t.stale_ij, sel, False)
            st_ji = _take_padded(ev_t.stale_ji, sel, False)
            overflow += jnp.maximum(jnp.sum(rel) - E, 0)

            # --- communication: deliver into local receivers' slots
            f_i, f_j = fetch_q[i], fetch_q[j]
            msg_i = jnp.where(st_ij[:, None], ext_prev[f_i], ext[f_i])
            msg_j = jnp.where(st_ji[:, None], ext_prev[f_j], ext[f_j])
            row_j = jnp.where(d_ij & (f_j < m), f_j, m)
            row_i = jnp.where(d_ji & (f_i < m), f_i, m)
            # scatter: last-write-wins — a repeated edge in one batch lands
            # the batch-order winner (mirrors the dense scenario engine)
            K = K.at[row_j, r].set(msg_i, mode="drop")
            K = K.at[row_i, s].set(msg_j, mode="drop")  # scatter: last-write-wins

            # --- update: compact local endpoints, shared Eq. (6) step
            f_u = jnp.concatenate([f_i, f_j])
            got = jnp.concatenate([d_ji, d_ij]) & (f_u < m)
            usel = jnp.nonzero(got, size=U, fill_value=2 * E)[0]
            lu = _take_padded(f_u, usel, m)
            lu_c = jnp.minimum(lu, m - 1)
            new = batched_model_update(nbr_p_blk[lu_c], K[lu_c], c_blk[lu_c],
                                       sol_blk[lu_c], alpha)
            # scatter: idempotent — duplicate rows in lu recompute the same
            # value from the same post-communication K
            theta = theta.at[jnp.where(lu < m, lu, m)].set(new, mode="drop")
            overflow += jnp.maximum(jnp.sum(got) - U, 0)
            if tel:
                stale, updates = tstate
                stale = tmetrics.staleness_step(stale, got, f_u, m)
                updates = updates + jnp.sum(got)
                tstate = (stale, updates)
            return (theta, K, ext, overflow, *tstate), None

        def outer(carry, ev_blk):
            carry, _ = jax.lax.scan(round_fn, carry, ev_blk)
            if tel:
                obj = tmetrics.mp_local_objective(
                    carry[0], carry[1], nbr_p_blk, c_blk, sol_blk, alpha)
                stale, updates = carry[4:]
                return carry, (carry[0], obj, stale, updates[None])
            return carry, carry[0]

        ext0 = exchange_halo(theta0_blk)                 # = warm-start halo
        carry0 = (theta0_blk, K0_blk, ext0, jnp.int32(0))
        if tel:
            carry0 = carry0 + (jnp.zeros((m,), jnp.int32), jnp.int32(0))
        carry, hist = jax.lax.scan(outer, carry0, ev)
        theta, overflow = carry[0], carry[3]
        if tel:
            hist, obj_h, stale_h, upd_h = hist
            return hist, theta, overflow[None], obj_h, stale_h, upd_h
        return hist, theta, overflow[None]

    ev_scan = _reshape_stream(stream, n_rec, record_every)
    out_specs = (P(None, AGENT_AXIS, None), P(AGENT_AXIS), P(AGENT_AXIS))
    if tel:
        out_specs = out_specs + (P(None, AGENT_AXIS), P(None, AGENT_AXIS),
                                 P(None, AGENT_AXIS))
    run = jax.shard_map(
        block_fn, mesh=mesh, check_vma=False,
        in_specs=(_scan_specs(P(), ev_scan), P(AGENT_AXIS), P(AGENT_AXIS),
                  P(AGENT_AXIS), P(AGENT_AXIS), P(AGENT_AXIS),
                  P(AGENT_AXIS, None), P(AGENT_AXIS, None),
                  P(AGENT_AXIS, None), P(AGENT_AXIS, None)),
        out_specs=out_specs)
    return run(ev_scan, theta0, K0, nbr_p, c, sol, fetch, bnd_pos,
               halo_src_shard, halo_src_pos)


def run_mp_scenario_sharded(topo: SparseTopology, theta_sol, c, alpha: float,
                            conditions: NetworkConditions, rounds: int,
                            batch: int, seed: int = 0,
                            record_every: int = 10, *,
                            n_shards: Optional[int] = None, mesh=None,
                            assignment: Optional[np.ndarray] = None,
                            local_batch: Optional[int] = None,
                            exchange: str = "all_gather",
                            halo_codec="f32",
                            partition_seed: int = 0,
                            telemetry: Optional[TelemetryConfig] = None
                            ) -> ShardedSimTrace:
    """``run_mp_scenario`` over a graph partitioned across the sim mesh.

    Same scenario semantics and RNG schedule as the single-device engine —
    ``trace.theta_hist`` reproduces it exactly whenever ``trace.overflow``
    is 0 (see module docstring).  ``n_shards`` defaults to every local
    device; pass ``assignment`` to reuse a precomputed partition, and
    ``exchange="ring"`` for the ppermute halo path.  ``halo_codec``
    selects the boundary-row wire format (``launch.sim_mesh.HaloCodec``:
    "f32" — the default, bit-for-bit with the single-device trajectory —
    or the lossy "bf16"/"int8" encodings with f32 accumulation); the
    telemetry ``halo_bytes`` column accounts the coded wire size.
    """
    mesh, P_, assignment, part = _sharded_setup(
        topo, n_shards, mesh, assignment, partition_seed)

    tabs = topo.tables
    n = topo.n
    theta_sol = np.asarray(theta_sol, np.float32).reshape(n, -1)
    c = np.asarray(c, np.float32)
    record_every, n_rec = record_chunks(rounds, record_every)
    total_rounds = n_rec * record_every

    stream = precompute_event_stream(
        topo.device_tables(), jnp.asarray(topo.partition_halves()),
        conditions, batch, seed, total_rounds)

    K0 = theta_sol[tabs.nbr_idx]                     # warm start (§3.2)
    sharded = dict(
        theta0=part.shard_rows(theta_sol), K0=part.shard_rows(K0),
        nbr_p=part.shard_rows(tabs.nbr_p), c=part.shard_rows(c),
        sol=part.shard_rows(theta_sol))
    E, U = _local_capacities(batch, P_, local_batch)

    tel = telemetry_on(telemetry)
    codec = resolve_halo_codec(halo_codec)
    outs = _sharded_scenario_scan(
        mesh, stream, **{k: _on_shards(mesh, v) for k, v in sharded.items()},
        **_partition_args(mesh, part),
        alpha=alpha, m=part.shard_size, H=part.halo_size,
        E=E, U=U, n_rec=n_rec, record_every=record_every,
        exchange=exchange, codec=codec, tel=tel)
    frames = None
    if tel:
        hist, theta, overflow, obj_h, stale_h, upd_h = outs
        frames = _sharded_frames(
            part, stream, n_rec, record_every, obj_h, stale_h, upd_h,
            overflow,
            payload_row_bytes=codec.row_nbytes((theta_sol.shape[1],)))
    else:
        hist, theta, overflow = outs

    delivered, dropped, invalid = stream_totals(stream)
    active_hist = np.asarray(stream.active_frac).reshape(
        n_rec, record_every)[:, -1]
    return ShardedSimTrace(
        theta_hist=part.unshard_rows(np.asarray(hist)),
        active_hist=active_hist, delivered=delivered, dropped=dropped,
        rounds=total_rounds, events=total_rounds * batch, invalid=invalid,
        telemetry=frames, n_shards=P_, edge_cut=part.edge_cut,
        halo_size=part.halo_size, local_batch=U,
        overflow=int(np.asarray(overflow).sum()))


# ---------------------------------------------------------------------------
# Sharded CL-ADMM scenario engine (DESIGN.md §12)
# ---------------------------------------------------------------------------


@partial(jax.jit,
         static_argnames=("mesh", "mu", "rho", "k", "m", "H", "E", "U",
                          "n_rec", "record_every", "exchange", "codec",
                          "tel", "primal"))
def _sharded_cl_scan(mesh, stream, theta0, K0, nbr_w, deg_count, D,
                     m_counts, sx, fetch, bnd_pos, halo_src_shard,
                     halo_src_pos,
                     tel_args=(), xym=(), *,
                     mu: float, rho: float, k: int, m: int, H: int, E: int,
                     U: int, n_rec: int, record_every: int, exchange: str,
                     codec: HaloCodec = HaloCodec("f32"),
                     tel: bool = False, primal=None):
    """shard_map'd CL-ADMM rounds: the six ADMM state arrays are row-sharded
    (P * m leading axis); the event stream is replicated and replayed per
    shard exactly as the MP engine does.  Only the warm-start models and
    neighbour copies come in as arguments; each shard builds the rest of
    its state in-jit (``engines.warm_admm_from_copies``).

    Edge state never leaves its owner: for a cross-shard edge each endpoint
    shard keeps its own (Z_own, Z_nbr, L_own, L_nbr) slots and mirrors the
    partner's payload — post-primal theta + K plus round-start duals — into
    its halo via one exchange per round, placed *between* the primal and
    edge phases (the edge half-step reads post-primal remote models).  The
    previous round's ext buffer serves the one-round-stale payloads.

    ``primal`` (static) mirrors ``engines._cl_scenario_scan``: ``None``
    keeps the inline exact quadratic solve; a data-hungry PrimalSolver
    receives the rows' padded local data via the row-sharded ``xym``
    blocks (the solve is row-local, so sharding it is free).
    """
    P_ = mesh_shards(mesh)
    batch = stream.i.shape[-1]
    n_xym = 3 if (primal is not None and primal.needs_data) else 0

    def block_fn(ev, theta0_blk, K0_blk, w_blk, degc_blk, D_blk, mc_blk,
                 sx_blk, fetch_blk, bnd_blk, hsrc_blk, hpos_blk,
                 *extra_blks):
        xym_blk = extra_blks[:n_xym]
        tel_blks = extra_blks[n_xym:]
        fetch_q = fetch_blk[0]
        bnd = bnd_blk[0]
        hsrc, hpos = hsrc_blk[0], hpos_blk[0]
        exchange_halo = halo_exchange_fn(bnd, hsrc, hpos, H, P_, exchange,
                                         codec=codec)
        live_blk = jnp.arange(k)[None, :] < degc_blk[:, None]      # (m, k)

        def publish(theta, K, Lo, Ln):
            """Stacked payload rows [theta | K | L_own | L_nbr] -> ext."""
            pub = jnp.concatenate([theta[:, None, :], K, Lo, Ln], axis=1)
            return exchange_halo(pub)                  # (m + H + 1, 1+3k, p)

        def round_fn(carry, ev_t):
            theta, K, Zo, Zn, Lo, Ln, ext_prev, overflow, *tstate = carry

            # --- compact to the events touching this shard (O(E) ~ 2B/P)
            rel = (fetch_q[ev_t.i] < m) | (fetch_q[ev_t.j] < m)
            sel = jnp.nonzero(rel, size=E, fill_value=batch)[0]
            i = _take_padded(ev_t.i, sel, 0)
            j = _take_padded(ev_t.j, sel, 0)
            s = _take_padded(ev_t.s, sel, 0)
            r = _take_padded(ev_t.r, sel, 0)
            d_ij = _take_padded(ev_t.deliver_ij, sel, False)
            d_ji = _take_padded(ev_t.deliver_ji, sel, False)
            st_ij = _take_padded(ev_t.stale_ij, sel, False)
            st_ji = _take_padded(ev_t.stale_ji, sel, False)
            overflow += jnp.maximum(jnp.sum(rel) - E, 0)

            # --- primal phase: compact local handshake endpoints, shared
            # exact quadratic step (core.sparse.batched_admm_primal)
            f_i, f_j = fetch_q[i], fetch_q[j]
            f_u = jnp.concatenate([f_i, f_j])                     # (2E,)
            got = jnp.concatenate([d_ji, d_ij]) & (f_u < m)
            usel = jnp.nonzero(got, size=U, fill_value=2 * E)[0]
            lu = _take_padded(f_u, usel, m)
            lu_c = jnp.minimum(lu, m - 1)
            if primal is None:
                new_theta, theta_js = batched_admm_primal(
                    w_blk[lu_c], live_blk[lu_c], Zo[lu_c], Zn[lu_c],
                    Lo[lu_c], Ln[lu_c], D_blk[lu_c], mc_blk[lu_c],
                    sx_blk[lu_c], mu, rho)
            else:
                xr = tuple(a[lu_c] for a in xym_blk)
                new_theta, theta_js = primal.solve_batch(
                    w_blk[lu_c], live_blk[lu_c], Zo[lu_c], Zn[lu_c],
                    Lo[lu_c], Ln[lu_c], D_blk[lu_c], mc_blk[lu_c],
                    sx_blk[lu_c], xr, theta[lu_c], mu, rho, None)
            new_K = jnp.where(live_blk[lu_c][:, :, None], theta_js, K[lu_c])
            rowp = jnp.where(lu < m, lu, m)
            # scatter: idempotent — duplicate rows in lu derive identical
            # values from the same round-start Z/L state
            theta = theta.at[rowp].set(new_theta, mode="drop")
            K = K.at[rowp].set(new_K, mode="drop")  # scatter: idempotent
            overflow += jnp.maximum(jnp.sum(got) - U, 0)

            # --- publish + halo exchange (post-primal models, round-start
            # duals), then the edge phase reads payloads from ext
            ext = publish(theta, K, Lo, Ln)

            # --- edge phase: one half-step per delivered direction whose
            # receiver is local
            own_s = jnp.concatenate([s, r])
            oth_f = jnp.concatenate([f_j, f_i])
            oth_s = jnp.concatenate([r, s])
            stale = jnp.concatenate([st_ji, st_ij])[:, None, None]
            pay = jnp.where(stale, ext_prev[oth_f], ext[oth_f])
            ar = jnp.arange(oth_s.shape[0])
            th_pay = pay[:, 0]
            k_pay = pay[ar, 1 + oth_s]
            lo_pay = pay[ar, 1 + k + oth_s]
            ln_pay = pay[ar, 1 + 2 * k + oth_s]
            own_c = jnp.minimum(f_u, m - 1)
            z_own, z_nbr, lo_new, ln_new = admm_edge_halfstep(
                theta[own_c], K[own_c, own_s], Lo[own_c, own_s],
                Ln[own_c, own_s], th_pay, k_pay, lo_pay, ln_pay, rho)
            rowe = jnp.where(got, f_u, m)
            # scatter: unique targets — each event side writes its own
            # (row, slot) cell; a slot belongs to one edge and each edge
            # fires once per round
            Zo = Zo.at[rowe, own_s].set(z_own, mode="drop")
            Zn = Zn.at[rowe, own_s].set(z_nbr, mode="drop")  # scatter: unique targets
            Lo = Lo.at[rowe, own_s].set(lo_new, mode="drop")  # scatter: unique targets
            Ln = Ln.at[rowe, own_s].set(ln_new, mode="drop")  # scatter: unique targets
            if tel:
                stale, updates = tstate
                stale = tmetrics.staleness_step(stale, got, f_u, m)
                updates = updates + jnp.sum(got)
                tstate = (stale, updates)
            return (theta, K, Zo, Zn, Lo, Ln, ext, overflow, *tstate), None

        def outer(carry, ev_blk):
            carry, _ = jax.lax.scan(round_fn, carry, ev_blk)
            if tel:
                if primal is not None and primal.needs_data:
                    loss_vec = primal.batch_local_loss(carry[0], *xym_blk)
                    obj = tmetrics.cl_local_objective_from_loss(
                        carry[0], carry[1], w_blk, live_blk, D_blk,
                        loss_vec, mu)
                else:
                    (sxx_blk,) = tel_blks
                    obj = tmetrics.cl_local_objective(
                        carry[0], carry[1], w_blk, live_blk, D_blk, mc_blk,
                        sx_blk, sxx_blk, mu)
                stale, updates = carry[8:]
                return carry, (carry[0], obj, stale, updates[None])
            return carry, carry[0]

        st0 = warm_admm_from_copies(theta0_blk, K0_blk)
        ext0 = publish(st0.theta, st0.K, st0.L_own, st0.L_nbr)  # warm halo
        carry0 = (st0.theta, st0.K, st0.Z_own, st0.Z_nbr, st0.L_own,
                  st0.L_nbr, ext0, jnp.int32(0))
        if tel:
            carry0 = carry0 + (jnp.zeros((m,), jnp.int32), jnp.int32(0))
        carry, hist = jax.lax.scan(outer, carry0, ev)
        theta, overflow = carry[0], carry[7]
        if tel:
            hist, obj_h, stale_h, upd_h = hist
            return hist, theta, overflow[None], obj_h, stale_h, upd_h
        return hist, theta, overflow[None]

    ev_scan = _reshape_stream(stream, n_rec, record_every)
    row = P(AGENT_AXIS)
    per_shard = P(AGENT_AXIS, None)
    out_specs = (P(None, AGENT_AXIS, None), row, row)
    if tel:
        out_specs = out_specs + (P(None, AGENT_AXIS),) * 3
    run = jax.shard_map(
        block_fn, mesh=mesh, check_vma=False,
        in_specs=(_scan_specs(P(), ev_scan),) + (row,) * 7
        + (per_shard,) * 4 + (row,) * n_xym + (row,) * len(tel_args),
        out_specs=out_specs)
    return run(ev_scan, theta0, K0, nbr_w, deg_count,
               D, m_counts, sx, fetch, bnd_pos, halo_src_shard,
               halo_src_pos, *xym, *tel_args)


def run_cl_scenario_sharded(topo: SparseTopology, data, mu: float,
                            rho: float, conditions: NetworkConditions,
                            rounds: int, batch: int, seed: int = 0,
                            record_every: int = 10, *, theta_sol=None,
                            n_shards: Optional[int] = None, mesh=None,
                            assignment: Optional[np.ndarray] = None,
                            local_batch: Optional[int] = None,
                            exchange: str = "all_gather",
                            halo_codec="f32",
                            partition_seed: int = 0,
                            stream: Optional[EventStream] = None,
                            telemetry: Optional[TelemetryConfig] = None,
                            primal=None) -> ShardedSimTrace:
    """``simulate.engines.run_cl_scenario`` over a graph partitioned across
    the sim mesh.

    Same scenario semantics and RNG schedule as the single-device CL-ADMM
    engine — ``trace.theta_hist`` reproduces it exactly whenever
    ``trace.overflow`` is 0.  The six sparse ADMM state arrays are
    row-sharded; per round one halo exchange mirrors each boundary agent's
    post-primal (theta, K) and round-start (L_own, L_nbr) rows onto the
    shards that hold the other endpoint of its cross-shard edges, and each
    shard then applies the shared edge half-step to its own slots only
    (DESIGN.md §12).  Knobs match ``run_mp_scenario_sharded``, including
    ``halo_codec`` — here the codec covers the full stacked
    ``[theta | K | L_own | L_nbr]`` payload rows, with one int8 scale per
    model/dual component.

    ``primal`` selects the primal-phase solver exactly as in
    ``engines.run_cl_scenario`` (``core.primal``); the primal solve is
    row-local, so the inexact solver shards the same way the exact one
    does — the agents' padded local datasets are row-sharded alongside
    the ADMM state.
    """
    mesh, P_, assignment, part = _sharded_setup(
        topo, n_shards, mesh, assignment, partition_seed)

    tabs = topo.tables
    record_every, n_rec = record_chunks(rounds, record_every)
    total_rounds = n_rec * record_every

    if stream is None:
        stream = precompute_event_stream(
            topo.device_tables(), jnp.asarray(topo.partition_halves()),
            conditions, batch, seed, total_rounds)
    else:
        if stream.i.shape[0] != total_rounds:
            raise ValueError(
                f"stream covers {stream.i.shape[0]} rounds but the clamped "
                f"horizon is {total_rounds}")
        batch = int(stream.i.shape[1])

    if theta_sol is None:
        raise ValueError("need theta_sol (warm start)")
    # warm start (paper §4.2): the neighbour copies are gathered on the
    # host (they cross shards); each shard derives the rest in-jit
    theta0 = np.asarray(theta_sol, np.float32).reshape(topo.n, -1)
    # the local-data reductions use the same jnp expressions as the
    # single-device engine (numpy's pairwise summation rounds differently,
    # which would break the bit-for-bit parity)
    mask = jnp.asarray(data.mask, jnp.float32)
    x = jnp.asarray(data.x, jnp.float32)
    m_counts = np.asarray(jnp.sum(mask, axis=1))
    sx = np.asarray(jnp.sum(x * mask[:, :, None], axis=1))
    needs_data = primal is not None and primal.needs_data
    xym = ()
    if needs_data:
        xym = tuple(_on_shards(mesh, part.shard_rows(np.asarray(a)))
                    for a in (x, jnp.asarray(data.y, jnp.float32), mask))
    sharded = dict(
        theta0=part.shard_rows(theta0),
        K0=part.shard_rows(theta0[tabs.nbr_idx]),
        nbr_w=part.shard_rows(tabs.nbr_w),
        deg_count=part.shard_rows(tabs.deg_count),
        D=part.shard_rows(tabs.deg_w.astype(np.float32)),
        m_counts=part.shard_rows(m_counts),
        sx=part.shard_rows(sx))
    E, U = _local_capacities(batch, P_, local_batch)

    tel = telemetry_on(telemetry)
    tel_args = ()
    if tel and not needs_data:
        sxx = np.asarray(jnp.sum(mask * jnp.sum(x * x, axis=-1), axis=1))
        tel_args = (_on_shards(mesh, part.shard_rows(sxx)),)
    codec = resolve_halo_codec(halo_codec)
    outs = _sharded_cl_scan(
        mesh, stream,
        **{k_: _on_shards(mesh, v) for k_, v in sharded.items()},
        **_partition_args(mesh, part), tel_args=tel_args,
        xym=xym,
        mu=mu, rho=rho, k=topo.k_max, m=part.shard_size, H=part.halo_size,
        E=E, U=U, n_rec=n_rec, record_every=record_every,
        exchange=exchange, codec=codec, tel=tel, primal=primal)
    frames = None
    if tel:
        hist, theta, overflow, obj_h, stale_h, upd_h = outs
        p_dim = int(theta0.shape[1])
        frames = _sharded_frames(
            part, stream, n_rec, record_every, obj_h, stale_h, upd_h,
            overflow,
            payload_row_bytes=codec.row_nbytes((1 + 3 * topo.k_max, p_dim)))
    else:
        hist, theta, overflow = outs

    delivered, dropped, invalid = stream_totals(stream)
    active_hist = np.asarray(stream.active_frac).reshape(
        n_rec, record_every)[:, -1]
    return ShardedSimTrace(
        theta_hist=part.unshard_rows(np.asarray(hist)),
        active_hist=active_hist, delivered=delivered, dropped=dropped,
        rounds=total_rounds, events=total_rounds * batch, invalid=invalid,
        telemetry=frames, n_shards=P_, edge_cut=part.edge_cut,
        halo_size=part.halo_size, local_batch=U,
        overflow=int(np.asarray(overflow).sum()))


# ---------------------------------------------------------------------------
# Sharded joint model + collaboration-graph learning (DESIGN.md §13)
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class JointShardedTrace(ShardedSimTrace):
    """ShardedSimTrace plus graph-learning outputs and re-compaction stats.

    Fields mirror ``engines.JointSimTrace``; ``recompactions`` counts halo
    re-compactions performed (each shrinks ``halo_size`` to the live cross
    edges at that point — the reported ``halo_size`` is the final one).
    """

    final_w: Optional[np.ndarray] = None
    final_live: Optional[np.ndarray] = None
    live_edges_hist: Optional[np.ndarray] = None
    suppressed: int = 0
    recompactions: int = 0


def _slice_stream(stream: EventStream, lo: int, hi: int):
    """Rounds [lo, hi) of a materialized stream (active_frac dropped)."""
    return jax.tree_util.tree_map(lambda x: x[lo:hi],
                                  stream._replace(active_frac=None))


def _live_cross_edges(tabs, owner: np.ndarray, live: np.ndarray) -> int:
    """Directed live candidate slots whose sender lives on another shard
    (the same edge enumeration ``GraphPartition.build`` compacts halos
    from, so the re-compaction trigger and the rebuild always agree)."""
    src, dst = _directed_edges(tabs, live)
    return int((owner[src] != owner[dst]).sum())


@partial(jax.jit,
         static_argnames=("mesh", "alpha", "eta_graph", "lam", "graph_every",
                          "prune_eps", "m", "H", "E", "U", "n_rec",
                          "record_every", "exchange", "codec", "backend",
                          "tel"))
def _sharded_joint_scan(mesh, stream, ts, theta0, K0, theta_prev0, w0,
                        live0, c, sol, fetch, bnd_pos, halo_src_shard,
                        halo_src_pos, tel_args=(), *, alpha: float,
                        eta_graph: float, lam: float, graph_every: int,
                        prune_eps, m: int, H: int, E: int, U: int,
                        n_rec: int, record_every: int, exchange: str,
                        codec: HaloCodec = HaloCodec("f32"),
                        backend=None, tel: bool = False):
    """One jitted *segment* of the sharded joint engine.

    The MP round structure of ``_sharded_scenario_scan`` with the mixing
    weights ``w`` and candidate-liveness ``live`` promoted to row-sharded
    scan state, the shared graph step (``core.graph_learning``) applied to
    each shard's local rows every ``graph_every``-th global round, and
    deliveries into pruned receiver slots voided (counted in
    ``suppressed``).  ``theta_prev`` (the previous round's round-start
    models) rides the carry so the driver can rebuild the stale-payload
    ext buffer after a halo re-compaction changes ``H`` between segments.

    ``tel`` (static) threads each shard's staleness counters through the
    segment (``tel_args = (stale0,)`` row-sharded in, final counters out)
    and adds per-chunk (objective, staleness, updates, suppressed) block
    outputs, so segments compose into exactly the single-device
    accumulators.
    """
    P_ = mesh_shards(mesh)
    batch = stream.i.shape[-1]
    prune = eta_graph > 0.0 and prune_eps is not None

    def block_fn(ev, ts_blk, theta0_blk, K0_blk, thp0_blk, w0_blk, live0_blk,
                 c_blk, sol_blk, fetch_blk, bnd_blk, hsrc_blk, hpos_blk,
                 *tel_blks):
        fetch_q = fetch_blk[0]
        bnd = bnd_blk[0]
        hsrc, hpos = hsrc_blk[0], hpos_blk[0]
        exchange_halo = halo_exchange_fn(bnd, hsrc, hpos, H, P_, exchange,
                                         codec=codec)

        def round_fn(carry, inp):
            theta, K, theta_prev, w, live, ext_prev, suppressed, overflow, \
                *tstate = carry
            ev_t, t = inp
            theta_in = theta
            ext = exchange_halo(theta)

            # --- compact to the events touching this shard (O(E) ~ 2B/P)
            rel = (fetch_q[ev_t.i] < m) | (fetch_q[ev_t.j] < m)
            sel = jnp.nonzero(rel, size=E, fill_value=batch)[0]
            i = _take_padded(ev_t.i, sel, 0)
            j = _take_padded(ev_t.j, sel, 0)
            s = _take_padded(ev_t.s, sel, 0)
            r = _take_padded(ev_t.r, sel, 0)
            d_ij = _take_padded(ev_t.deliver_ij, sel, False)
            d_ji = _take_padded(ev_t.deliver_ji, sel, False)
            st_ij = _take_padded(ev_t.stale_ij, sel, False)
            st_ji = _take_padded(ev_t.stale_ji, sel, False)
            overflow += jnp.maximum(jnp.sum(rel) - E, 0)

            # --- communication (receiver-side pruning voids a delivery)
            f_i, f_j = fetch_q[i], fetch_q[j]
            if prune:
                lv_j = live[jnp.minimum(f_j, m - 1), r] & (f_j < m)
                lv_i = live[jnp.minimum(f_i, m - 1), s] & (f_i < m)
                ok_ij = d_ij & lv_j
                ok_ji = d_ji & lv_i
                suppressed = suppressed \
                    + jnp.sum(d_ij & (f_j < m) & ~lv_j) \
                    + jnp.sum(d_ji & (f_i < m) & ~lv_i)
            else:
                ok_ij, ok_ji = d_ij, d_ji
            msg_i = jnp.where(st_ij[:, None], ext_prev[f_i], ext[f_i])
            msg_j = jnp.where(st_ji[:, None], ext_prev[f_j], ext[f_j])
            row_j = jnp.where(ok_ij & (f_j < m), f_j, m)
            row_i = jnp.where(ok_ji & (f_i < m), f_i, m)
            # scatter: last-write-wins — a repeated edge in one batch lands
            # the batch-order winner (mirrors the dense scenario engine)
            K = K.at[row_j, r].set(msg_i, mode="drop")
            K = K.at[row_i, s].set(msg_j, mode="drop")  # scatter: last-write-wins

            # --- update: compact local endpoints, shared Eq. (6) step
            # under the current learned weights
            f_u = jnp.concatenate([f_i, f_j])
            got = jnp.concatenate([ok_ji, ok_ij]) & (f_u < m)
            usel = jnp.nonzero(got, size=U, fill_value=2 * E)[0]
            lu = _take_padded(f_u, usel, m)
            lu_c = jnp.minimum(lu, m - 1)
            new = batched_model_update(w[lu_c], K[lu_c], c_blk[lu_c],
                                       sol_blk[lu_c], alpha, backend)
            # scatter: idempotent — duplicate rows in lu recompute the same
            # value from the same post-communication K
            theta = theta.at[jnp.where(lu < m, lu, m)].set(new, mode="drop")
            overflow += jnp.maximum(jnp.sum(got) - U, 0)

            # --- graph step over this shard's local rows (compiled out at
            # rate 0; identical row arithmetic to the single-device engine)
            if eta_graph > 0.0:
                def do_graph(w, live):
                    w2 = reweight_rows(theta, K, w, live, eta=eta_graph,
                                       lam=lam, backend=backend)
                    if prune_eps is not None:
                        return prune_rows(w2, live, prune_eps)
                    return w2, live

                w, live = jax.lax.cond(
                    (t + 1) % graph_every == 0, do_graph,
                    lambda w, live: (w, live), w, live)

            if tel:
                stale, updates = tstate
                stale = tmetrics.staleness_step(stale, got, f_u, m)
                updates = updates + jnp.sum(got)
                tstate = (stale, updates)
            return (theta, K, theta_in, w, live, ext, suppressed,
                    overflow, *tstate), None

        def outer(carry, inp):
            carry, _ = jax.lax.scan(round_fn, carry, inp)
            theta, _, _, w, live = carry[:5]
            edges = jnp.sum(live & (w > 0))[None]
            if tel:
                obj = tmetrics.mp_local_objective(
                    theta, carry[1], jnp.where(live, w, 0.0), c_blk,
                    sol_blk, alpha)
                stale, updates = carry[8:]
                return carry, (theta, edges, obj, stale, updates[None],
                               carry[6][None])
            return carry, (theta, edges)

        ext_prev0 = exchange_halo(thp0_blk)
        carry0 = (theta0_blk, K0_blk, thp0_blk, w0_blk, live0_blk, ext_prev0,
                  jnp.int32(0), jnp.int32(0))
        if tel:
            carry0 = carry0 + (tel_blks[0], jnp.int32(0))
        carry, hist = jax.lax.scan(outer, carry0, (ev, ts_blk))
        theta, K, theta_prev, w, live, _, suppressed, overflow = carry[:8]
        base = (hist[0], hist[1], theta, K, theta_prev, w, live,
                suppressed[None], overflow[None])
        if tel:
            return base + (hist[2], hist[3], hist[4], hist[5], carry[8])
        return base

    ev_scan = _reshape_stream(stream, n_rec, record_every)
    row = P(AGENT_AXIS)
    per_shard = P(AGENT_AXIS, None)
    out_specs = (P(None, AGENT_AXIS, None), P(None, AGENT_AXIS)) \
        + (row,) * 5 + (P(AGENT_AXIS),) * 2
    if tel:
        out_specs = out_specs + (P(None, AGENT_AXIS),) * 4 + (row,)
    run = jax.shard_map(
        block_fn, mesh=mesh, check_vma=False,
        in_specs=(_scan_specs(P(), ev_scan), P()) + (row,) * 7
        + (per_shard,) * 4 + (row,) * len(tel_args),
        out_specs=out_specs)
    return run(ev_scan, ts, theta0, K0, theta_prev0, w0, live0, c, sol,
               fetch, bnd_pos, halo_src_shard, halo_src_pos, *tel_args)


def run_joint_scenario_sharded(topo: SparseTopology, theta_sol, c,
                               alpha: float, conditions: NetworkConditions,
                               rounds: int, batch: int, seed: int = 0,
                               record_every: int = 10, *,
                               eta_graph: float = 0.0, lam: float = 1.0,
                               graph_every: int = 1,
                               prune_eps: Optional[float] = None,
                               recompact_every: Optional[int] = None,
                               recompact_frac: float = 0.25,
                               n_shards: Optional[int] = None, mesh=None,
                               assignment: Optional[np.ndarray] = None,
                               local_batch: Optional[int] = None,
                               exchange: str = "all_gather",
                               halo_codec="f32",
                               partition_seed: int = 0,
                               stream: Optional[EventStream] = None,
                               backend=None,
                               telemetry: Optional[TelemetryConfig] = None
                               ) -> JointShardedTrace:
    """``engines.run_joint_scenario`` over a graph partitioned across the
    sim mesh (DESIGN.md §13).

    Same scenario semantics and RNG schedule as the single-device joint
    engine — ``trace.theta_hist`` (and the learned ``final_w``/``final_live``)
    reproduce it exactly whenever ``trace.overflow`` is 0.  The learned
    weights and candidate-liveness are row-sharded scan state; the graph
    step is row-local, so it needs no extra collective.

    **Halo re-compaction**: with pruning enabled (``prune_eps``) and a
    ``recompact_every`` (rounds) cadence, the driver pauses between jitted
    segments, measures how many *cross-shard* candidate slots the graph
    step has pruned, and — once the live cross-edge count has dropped by
    ``recompact_frac`` since the last layout — rebuilds the halo/boundary
    tables restricted to live edges (``GraphPartition.build(live=...)``).
    Pruning is monotone (``core.graph_learning.prune_rows``), so dropped
    halo rows are never needed again and the trajectory is unaffected;
    only the exchange volume shrinks.  ``rounds`` is first floored by the
    shared recording policy; segment boundaries land on record chunks.
    """
    mesh, P_, assignment, part = _sharded_setup(
        topo, n_shards, mesh, assignment, partition_seed)
    owner = np.asarray(assignment, np.int32)
    full_cut = part.edge_cut

    tabs = topo.tables
    n = topo.n
    theta_sol = np.asarray(theta_sol, np.float32).reshape(n, -1)
    c = np.asarray(c, np.float32)
    record_every, n_rec = record_chunks(rounds, record_every)
    total_rounds = n_rec * record_every

    if stream is None:
        stream = precompute_event_stream(
            topo.device_tables(), jnp.asarray(topo.partition_halves()),
            conditions, batch, seed, total_rounds)
    else:
        if stream.i.shape[0] != total_rounds:
            raise ValueError(
                f"stream covers {stream.i.shape[0]} rounds but the clamped "
                f"horizon is {total_rounds}")
        batch = int(stream.i.shape[1])

    K0 = theta_sol[tabs.nbr_idx]                     # warm start (§3.2)
    live0 = np.asarray(live_slots(tabs.deg_count, tabs.k_max))
    theta = _on_shards(mesh, part.shard_rows(theta_sol))
    K = _on_shards(mesh, part.shard_rows(K0))
    theta_prev = theta
    w = _on_shards(mesh, part.shard_rows(tabs.nbr_p))
    live = _on_shards(mesh, part.shard_rows(live0))
    c_sh = _on_shards(mesh, part.shard_rows(c))
    sol_sh = _on_shards(mesh, part.shard_rows(theta_sol))
    E, U = _local_capacities(batch, P_, local_batch)

    # segment schedule (record chunks per jitted call)
    can_recompact = (eta_graph > 0.0 and prune_eps is not None
                     and recompact_every is not None)
    if can_recompact:
        # repro-lint: disable=RPL007  n_rec already record_chunks-normalized
        seg = recompact_every // record_every
        seg_rec = max(1, min(n_rec, seg))
    else:
        seg_rec = n_rec
    cross_at_compact = _live_cross_edges(tabs, owner, live0)

    tel = telemetry_on(telemetry)
    codec = resolve_halo_codec(halo_codec)
    p_dim = theta_sol.shape[1]
    stale = jnp.zeros((P_ * part.shard_size,), jnp.int32) if tel else None
    tel_obj, tel_stale, tel_upd, tel_sup, tel_halo = [], [], [], [], []
    upd_off = sup_off = halo_off = 0
    ovf_shards = np.zeros(P_, np.int64)

    hists, live_hists = [], []
    suppressed = overflow = recompactions = 0
    done = 0
    while done < n_rec:
        seg = min(seg_rec, n_rec - done)
        ev_seg = _slice_stream(stream, done * record_every,
                               (done + seg) * record_every)
        ts_seg = jnp.arange(done * record_every,
                            (done + seg) * record_every,
                            dtype=jnp.int32).reshape(seg, record_every)
        (hist, live_hist, theta, K, theta_prev, w, live, sup, ovf,
         *tel_out) = _sharded_joint_scan(
                mesh, ev_seg, ts_seg, theta, K, theta_prev, w, live,
                c_sh, sol_sh, *_partition_args(mesh, part).values(),
                (stale,) if tel else (), alpha=alpha,
                eta_graph=eta_graph, lam=lam, graph_every=graph_every,
                prune_eps=prune_eps, m=part.shard_size, H=part.halo_size,
                E=E, U=U, n_rec=seg, record_every=record_every,
                exchange=exchange, codec=codec, backend=backend, tel=tel)
        hists.append(np.asarray(hist))
        live_hists.append(np.asarray(live_hist).sum(axis=1))
        suppressed += int(np.asarray(sup).sum())
        overflow += int(np.asarray(ovf).sum())
        if tel:
            obj_h, stale_h, upd_h, sup_h, stale = tel_out
            tel_obj.append(np.asarray(obj_h))
            tel_stale.append(np.asarray(stale_h))
            seg_upd = np.asarray(upd_h, np.int64).sum(axis=1)
            tel_upd.append(upd_off + seg_upd)
            upd_off = int(tel_upd[-1][-1])
            seg_sup = np.asarray(sup_h, np.int64).sum(axis=1)
            tel_sup.append(sup_off + seg_sup)
            sup_off = int(tel_sup[-1][-1])
            # halo payload of *this* segment's layout (re-compaction
            # shrinks the boundary between segments)
            per_round = halo_payload_bytes(
                P_, part.boundary_size, codec.row_nbytes((p_dim,)),
                part.halo_size)
            seg_rounds = (np.arange(seg, dtype=np.int64) + 1) * record_every
            tel_halo.append(halo_off + seg_rounds * per_round)
            halo_off = int(tel_halo[-1][-1])
            ovf_shards += np.asarray(ovf, np.int64)
        done += seg
        if done < n_rec and can_recompact and cross_at_compact > 0:
            live_host = part.unshard_rows(np.asarray(live))
            cur_cross = _live_cross_edges(tabs, owner, live_host)
            if cur_cross <= (1.0 - recompact_frac) * cross_at_compact:
                part = GraphPartition.build(topo, assignment, P_,
                                            live=live_host)
                cross_at_compact = cur_cross
                recompactions += 1

    frames = None
    if tel:
        frames = TelemetryFrames(
            rounds=(np.arange(n_rec, dtype=np.int64) + 1) * record_every,
            objective=np.concatenate(tel_obj)[:, part.perm_slot],
            staleness=np.concatenate(tel_stale)[:, part.perm_slot],
            updates=np.concatenate(tel_upd),
            halo_bytes=np.concatenate(tel_halo),
            overflow_per_shard=ovf_shards,
            suppressed=np.concatenate(tel_sup),
            **tmetrics.stream_chunk_totals(stream, n_rec, record_every))

    delivered, dropped, invalid = stream_totals(stream)
    active_hist = np.asarray(stream.active_frac).reshape(
        n_rec, record_every)[:, -1]
    return JointShardedTrace(
        theta_hist=part.unshard_rows(np.concatenate(hists, axis=0)),
        active_hist=active_hist, delivered=delivered, dropped=dropped,
        rounds=total_rounds, events=total_rounds * batch, invalid=invalid,
        telemetry=frames, n_shards=P_, edge_cut=full_cut,
        halo_size=part.halo_size, local_batch=U, overflow=overflow,
        final_w=part.unshard_rows(np.asarray(w)),
        final_live=part.unshard_rows(np.asarray(live)),
        live_edges_hist=np.concatenate(live_hists),
        suppressed=suppressed, recompactions=recompactions)
