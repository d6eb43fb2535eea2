"""Sparse event-driven simulator: sparse<->dense equivalence, topology
generators, fault scenarios, event-sampling edge cases (degree-0 agents,
all-churned wake draws, the shared recording policy), and the edge-coloring
matching property."""

import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (async_admm, async_gossip, gaussian_kernel_graph,
                        pad_datasets, random_geometric_graph, ring_graph,
                        solitary_mean, synchronous)
from repro.core.sparse import (record_chunks, sample_event,
                               tables_from_adjacency)
from repro.kernels import ops, ref
from repro.simulate import (NetworkConditions, SparseTopology,
                            cluster_topology, draw_events, draw_slots,
                            draw_wakeups, get_scenario, list_scenarios,
                            precompute_event_stream,
                            random_geometric_topology, ring_topology,
                            run_mp_scenario, sparse_async_admm,
                            sparse_async_gossip, sparse_sync_mp)
from repro.telemetry.config import TelemetryConfig


def isolated_agent_topology(n: int = 12, iso: int = 5) -> SparseTopology:
    """A ring over all agents except ``iso``, which has degree 0."""
    nbrs, wts = [], []
    ring = [v for v in range(n) if v != iso]
    pos = {v: t for t, v in enumerate(ring)}
    m = len(ring)
    for v in range(n):
        if v == iso:
            nbrs.append(np.array([], np.int64))
            wts.append(np.ones(0))
            continue
        t = pos[v]
        nb = np.sort(np.unique([ring[(t - 1) % m], ring[(t + 1) % m]]))
        nbrs.append(nb)
        wts.append(np.ones(len(nb)))
    tabs = tables_from_adjacency(nbrs, wts, allow_isolated=True)
    return SparseTopology(tabs, (np.arange(n) * 2 >= n).astype(np.int32))


# ---------------------------------------------------------------------------
# sparse <-> dense trajectory equivalence (the tentpole invariant)
# ---------------------------------------------------------------------------


class TestSparseDenseEquivalence:
    def test_mp_gossip_bit_for_bit(self):
        """Same seed -> sparse engine reproduces the dense (n, n, p)
        async_gossip trajectory exactly, not just approximately."""
        g = random_geometric_graph(16, k=3, seed=1)
        rng = np.random.default_rng(0)
        sol = rng.standard_normal((16, 3)).astype(np.float32)
        c = rng.uniform(0.05, 1.0, 16).astype(np.float32)
        dense = async_gossip(g, sol, c, 0.9, steps=400, seed=3,
                             record_every=50)
        topo = SparseTopology.from_graph(g)
        sparse = sparse_async_gossip(topo, sol, c, 0.9, steps=400, seed=3,
                                     record_every=50)
        assert np.array_equal(dense.theta_hist, sparse.theta_hist)
        diag = dense.final_knowledge[np.arange(16), np.arange(16)]
        assert np.array_equal(diag, sparse.final_theta)
        # neighbor knowledge matches slot-for-slot too
        tabs = topo.tables
        for i in range(16):
            for s in range(tabs.deg_count[i]):
                assert np.array_equal(
                    dense.final_knowledge[i, tabs.nbr_idx[i, s]],
                    sparse.final_knowledge[i, s])

    def test_mp_gossip_record_every_one(self):
        g = ring_graph(8)
        rng = np.random.default_rng(1)
        sol = rng.standard_normal((8, 2)).astype(np.float32)
        c = np.ones(8, np.float32)
        dense = async_gossip(g, sol, c, 0.8, steps=64, seed=0, record_every=1)
        sparse = sparse_async_gossip(SparseTopology.from_graph(g), sol, c,
                                     0.8, steps=64, seed=0, record_every=1)
        assert np.array_equal(dense.theta_hist, sparse.theta_hist)

    def test_admm_bit_for_bit(self):
        """16-agent quadratic CL-ADMM: same-seed trajectories identical."""
        n = 16
        rng = np.random.default_rng(2)
        pts = rng.standard_normal((n, 2)) * 0.5
        g = gaussian_kernel_graph(pts, sigma=1.0)
        xs = [rng.standard_normal((int(rng.integers(1, 12)), 1))
              for _ in range(n)]
        data = pad_datasets(xs, [np.zeros(len(x)) for x in xs])
        sol = solitary_mean(data)
        dense = async_admm(g, data, 0.1, 1.0, "quadratic", steps=300, seed=5,
                           record_every=50, theta_sol=sol)
        sparse = sparse_async_admm(SparseTopology.from_graph(g), data, 0.1,
                                   1.0, steps=300, seed=5, record_every=50,
                                   theta_sol=sol)
        assert np.array_equal(dense.theta_hist, sparse.theta_hist)

    def test_sync_sweep_matches_dense_synchronous(self):
        g = random_geometric_graph(24, k=3, seed=2)
        rng = np.random.default_rng(3)
        sol = rng.standard_normal((24, 5)).astype(np.float32)
        c = rng.uniform(0.05, 1.0, 24).astype(np.float32)
        dense = np.asarray(synchronous(g, sol, c, 0.9, steps=50))
        sparse = np.asarray(sparse_sync_mp(SparseTopology.from_graph(g), sol,
                                           c, 0.9, sweeps=50))
        np.testing.assert_allclose(dense, sparse, atol=1e-5)


# ---------------------------------------------------------------------------
# topology container + generators
# ---------------------------------------------------------------------------


def _check_topology(topo):
    tabs = topo.tables
    n, k = tabs.n, tabs.k_max
    assert (tabs.deg_count >= 1).all()
    live = np.arange(k)[None, :] < tabs.deg_count[:, None]
    # pads carry zero weight and duplicate the last live neighbor
    assert (tabs.nbr_w[~live] == 0).all()
    assert (tabs.nbr_p[~live] == 0).all()
    # sorted, self-loop-free neighbor ids
    for i in range(n):
        d = tabs.deg_count[i]
        row = tabs.nbr_idx[i, :d]
        assert (np.diff(row) > 0).all()
        assert i not in row
    # rev_slot inverts the edge: nbr_idx[j, rev_slot[i, s]] == i
    i_idx = np.repeat(np.arange(n), k)
    s_idx = np.tile(np.arange(k), n)
    j_idx = tabs.nbr_idx[i_idx, s_idx]
    back = tabs.nbr_idx[j_idx, tabs.rev_slot[i_idx, s_idx]]
    assert (back == i_idx).all()
    # symmetric adjacency: j in N_i  =>  i in N_j (implied by rev check)
    # slot cdf is the uniform pi_i
    last = tabs.slot_cdf[np.arange(n), tabs.deg_count - 1]
    np.testing.assert_allclose(last, 1.0, atol=1e-5)


class TestTopology:
    def test_from_graph(self):
        _check_topology(SparseTopology.from_graph(
            random_geometric_graph(40, k=4, seed=0)))

    def test_ring(self):
        topo = ring_topology(64)
        assert topo.k_max == 2 and topo.n_edges == 64
        _check_topology(topo)

    def test_random_geometric_scales_without_dense_matrix(self):
        topo = random_geometric_topology(3000, k=6, seed=0)
        _check_topology(topo)
        assert topo.n == 3000
        assert topo.k_max < 64                      # O(n k) storage, not O(n^2)
        assert topo.state_bytes(32) < topo.dense_state_bytes(32) / 20

    def test_cluster(self):
        topo = cluster_topology(400, n_clusters=8, k_intra=4, bridges=3,
                                seed=0)
        _check_topology(topo)
        assert set(topo.groups.tolist()) == set(range(8))
        halves = topo.partition_halves()
        assert 0 < halves.sum() < 400

    def test_from_graph_matches_dense_quantities(self):
        g = gaussian_kernel_graph(np.random.default_rng(0).standard_normal(
            (12, 2)), sigma=1.0)
        tabs = SparseTopology.from_graph(g).tables
        np.testing.assert_allclose(tabs.deg_w, g.degrees)
        P = g.P
        for i in range(12):
            d = tabs.deg_count[i]
            np.testing.assert_allclose(tabs.nbr_p[i, :d],
                                       P[i, tabs.nbr_idx[i, :d]], rtol=1e-6)


# ---------------------------------------------------------------------------
# edge coloring: matchings are vertex-disjoint and cover E
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("seed", range(8))
def test_edge_coloring_matchings_cover_and_disjoint(seed):
    rng = np.random.default_rng(seed)
    n = int(rng.integers(4, 24))
    kind = seed % 3
    if kind == 0:
        g = ring_graph(n)
    elif kind == 1:
        g = random_geometric_graph(n, k=min(3, n - 1), seed=seed)
    else:
        g = gaussian_kernel_graph(rng.standard_normal((n, 2)), sigma=1.0)
    matchings = g.edge_coloring()
    seen = set()
    for matching in matchings:
        busy = set()
        for (i, j) in matching:
            assert i not in busy and j not in busy, "matching not disjoint"
            busy.update((i, j))
            seen.add((min(i, j), max(i, j)))
    assert seen == set(g.edges()), "matchings must cover E exactly"


# ---------------------------------------------------------------------------
# scheduler + fault scenarios
# ---------------------------------------------------------------------------


class TestScenarios:
    @pytest.fixture(scope="class")
    def setup(self):
        topo = random_geometric_topology(192, k=5, seed=0)
        rng = np.random.default_rng(0)
        sol = rng.standard_normal((192, 4)).astype(np.float32)
        c = rng.uniform(0.05, 1.0, 192).astype(np.float32)
        star = np.asarray(sparse_sync_mp(topo, sol, c, 0.9, sweeps=300))
        return topo, sol, c, star

    def test_registry_complete(self):
        assert {"clean", "lossy-10", "straggler-tail", "churn-5",
                "partition-heal"} <= set(list_scenarios())
        with pytest.raises(KeyError):
            get_scenario("nope")

    def test_clean_converges_to_fixed_point(self, setup):
        topo, sol, c, star = setup
        tr = run_mp_scenario(topo, sol, c, 0.9,
                             NetworkConditions(), rounds=250, batch=48,
                             seed=0, record_every=50)
        e0 = np.linalg.norm(sol - star)
        e1 = np.linalg.norm(tr.theta_hist[-1] - star)
        assert e1 < 0.1 * e0
        assert tr.dropped == 0
        assert tr.delivered == 2 * tr.events

    def test_lossy_still_converges(self, setup):
        topo, sol, c, star = setup
        tr = run_mp_scenario(topo, sol, c, 0.9,
                             NetworkConditions(drop_prob=0.1, stale_prob=0.05),
                             rounds=250, batch=48, seed=0, record_every=50)
        e0 = np.linalg.norm(sol - star)
        e1 = np.linalg.norm(tr.theta_hist[-1] - star)
        assert e1 < 0.2 * e0
        assert tr.dropped > 0
        assert tr.delivered + tr.dropped == 2 * tr.events

    def test_churn_deactivates_agents(self, setup):
        topo, sol, c, star = setup
        tr = run_mp_scenario(topo, sol, c, 0.9,
                             NetworkConditions(churn_rate=0.002),
                             rounds=200, batch=32, seed=1, record_every=50)
        assert tr.active_hist[-1] < 1.0
        assert np.isfinite(tr.theta_hist).all()

    def test_partition_drops_cross_half_traffic_then_heals(self, setup):
        topo, sol, c, star = setup
        cond = NetworkConditions(partition_start=50, partition_end=150)
        tr = run_mp_scenario(topo, sol, c, 0.9, cond, rounds=300, batch=48,
                             seed=0, record_every=50)
        assert tr.dropped > 0                       # cut edges during window
        e0 = np.linalg.norm(sol - star)
        e1 = np.linalg.norm(tr.theta_hist[-1] - star)
        assert e1 < 0.15 * e0                       # heals and converges

    def test_staleness_changes_trajectory_but_converges(self, setup):
        """stale deliveries must actually deliver old models (regression:
        the pre-round snapshot, not the post-update one, is the payload)."""
        topo, sol, c, star = setup
        clean = run_mp_scenario(topo, sol, c, 0.9, NetworkConditions(),
                                rounds=150, batch=32, seed=3, record_every=50)
        stale = run_mp_scenario(topo, sol, c, 0.9,
                                NetworkConditions(stale_prob=1.0),
                                rounds=150, batch=32, seed=3, record_every=50)
        assert not np.array_equal(clean.theta_hist, stale.theta_hist)
        e0 = np.linalg.norm(sol - star)
        e1 = np.linalg.norm(stale.theta_hist[-1] - star)
        assert e1 < 0.5 * e0                      # old news still converges

    def test_short_horizon_not_exceeded(self):
        """rounds < record_every must not silently run extra rounds."""
        topo = ring_topology(32)
        sol = np.ones((32, 2), np.float32)
        c = np.ones(32, np.float32)
        tr = run_mp_scenario(topo, sol, c, 0.9, NetworkConditions(),
                             rounds=5, batch=4, seed=0, record_every=10)
        assert tr.rounds == 5
        assert tr.events == 20

    def test_straggler_slows_convergence(self, setup):
        topo, sol, c, star = setup
        fast = run_mp_scenario(topo, sol, c, 0.9, NetworkConditions(),
                               rounds=120, batch=32, seed=2, record_every=40)
        slow = run_mp_scenario(
            topo, sol, c, 0.9,
            NetworkConditions(straggler_frac=0.5, straggler_factor=0.02),
            rounds=120, batch=32, seed=2, record_every=40)
        e_fast = np.linalg.norm(fast.theta_hist[-1] - star)
        e_slow = np.linalg.norm(slow.theta_hist[-1] - star)
        assert e_slow > e_fast


# ---------------------------------------------------------------------------
# event-sampling edge cases (ISSUE 4 bugfixes) + accounting invariants
# ---------------------------------------------------------------------------


class TestDegreeZeroEvents:
    """A degree-0 agent's wake-up must be a no-op, not a phantom edge.

    Pre-fix, ``min(s, deg - 1) = -1`` wrapped via negative indexing into the
    last pad slot and fabricated an edge to whatever id the zero-initialized
    pad row held (agent 0)."""

    def test_sample_event_slot_never_negative(self):
        topo = isolated_agent_topology(12, iso=5)
        tabs = topo.device_tables()
        hit_iso = False
        for seed in range(200):
            i, s = sample_event(jax.random.PRNGKey(seed), 12, tabs.slot_cdf,
                                tabs.deg_count)
            assert int(s) >= 0, seed
            hit_iso |= int(i) == 5
        assert hit_iso          # the draw does reach the isolated agent

    def test_draw_slots_degree_zero_clamped(self):
        deg = jnp.asarray([3, 0, 1], jnp.int32)
        i = jnp.asarray([1, 1, 0, 2], jnp.int32)
        s = draw_slots(jax.random.PRNGKey(0), i, deg)
        assert (np.asarray(s) >= 0).all()

    def test_exact_gossip_isolated_agent_untouched(self):
        topo = isolated_agent_topology(12, iso=5)
        rng = np.random.default_rng(0)
        sol = rng.standard_normal((12, 3)).astype(np.float32)
        c = rng.uniform(0.1, 1.0, 12).astype(np.float32)
        tr = sparse_async_gossip(topo, sol, c, 0.9, steps=400, seed=0,
                                 record_every=100)
        # the isolated agent keeps its solitary model (pre-fix, its wake-ups
        # fabricated an edge to agent 0 — the zero-initialized pad row id —
        # and both endpoints' models moved)
        np.testing.assert_array_equal(tr.final_theta[5], sol[5])
        assert np.isfinite(tr.theta_hist).all()

    def test_exact_admm_isolated_agent_untouched(self):
        topo = isolated_agent_topology(10, iso=3)
        rng = np.random.default_rng(1)
        xs = [rng.standard_normal((int(rng.integers(1, 6)), 2))
              for _ in range(10)]
        data = pad_datasets(xs, [np.zeros(len(x)) for x in xs])
        sol = np.asarray(solitary_mean(data), np.float32)
        tr = sparse_async_admm(topo, data, 0.1, 1.0, steps=200, seed=0,
                               record_every=50, theta_sol=sol)
        np.testing.assert_array_equal(np.asarray(tr.final.theta)[3], sol[3])
        assert np.isfinite(tr.theta_hist).all()

    def test_scenario_isolated_agent_is_invalid_not_dropped(self):
        topo = isolated_agent_topology(12, iso=5)
        rng = np.random.default_rng(0)
        sol = rng.standard_normal((12, 2)).astype(np.float32)
        c = rng.uniform(0.1, 1.0, 12).astype(np.float32)
        tr = run_mp_scenario(topo, sol, c, 0.9, NetworkConditions(),
                             rounds=50, batch=8, seed=0, record_every=10)
        # the isolated agent wakes sometimes: those events are invalid, not
        # lost messages, and its model never moves
        assert tr.invalid > 0
        assert tr.dropped == 0
        assert tr.delivered + tr.dropped == 2 * (tr.events - tr.invalid)
        np.testing.assert_array_equal(tr.theta_hist[-1][5], sol[5])


class TestAllChurnedWakeups:
    """When every agent is churned out the wake CDF is all-zero; pre-fix
    searchsorted deterministically picked agent n-1 and the dead events
    inflated ``dropped``."""

    def test_draw_wakeups_flags_dead_network(self):
        i, alive = draw_wakeups(jax.random.PRNGKey(0), jnp.zeros(16), 8)
        assert not bool(alive)
        i2, alive2 = draw_wakeups(jax.random.PRNGKey(0), jnp.ones(16), 8)
        assert bool(alive2)

    def test_draw_events_all_inactive_marks_invalid(self):
        topo = ring_topology(16)
        tabs = topo.device_tables()
        ev = draw_events(jax.random.PRNGKey(1), NetworkConditions(), tabs,
                         jnp.asarray(topo.partition_halves()),
                         jnp.zeros(16, bool), jnp.ones(16), 0, 8)
        assert not np.asarray(ev.valid).any()
        assert not np.asarray(ev.deliver_ij).any()

    def test_emptied_network_excluded_from_counters(self):
        """churn_rate high enough to empty a 4-agent ring for some rounds:
        the dead-round draws are invalid and charged to neither counter."""
        topo = ring_topology(4)
        sol = np.ones((4, 2), np.float32)
        c = np.ones(4, np.float32)
        tr = run_mp_scenario(topo, sol, c, 0.9,
                             NetworkConditions(churn_rate=0.9), rounds=60,
                             batch=4, seed=0, record_every=10)
        assert tr.invalid > 0
        assert tr.delivered + tr.dropped == 2 * (tr.events - tr.invalid)
        assert np.isfinite(tr.theta_hist).all()
        # the materialized stream agrees event-for-event
        stream = precompute_event_stream(
            topo.device_tables(), jnp.asarray(topo.partition_halves()),
            NetworkConditions(churn_rate=0.9), 4, 0, tr.rounds)
        assert int((~np.asarray(stream.valid)).sum()) == tr.invalid
        delivered = int(np.asarray(stream.deliver_ij).sum()
                        + np.asarray(stream.deliver_ji).sum())
        assert delivered == tr.delivered


class TestRecordingPolicy:
    """All six ``// record_every`` sites share ``record_chunks``: clamp to
    [1, steps], floor to whole chunks — never zero steps, never an overrun."""

    def test_record_chunks_contract(self):
        assert record_chunks(5, 100) == (5, 1)      # clamp: steps < every
        assert record_chunks(7, 5) == (5, 1)        # floor: non-divisible
        assert record_chunks(100, 10) == (10, 10)   # divisible: unchanged
        assert record_chunks(1, 1) == (1, 1)
        with pytest.raises(ValueError):
            record_chunks(0, 10)

    def test_short_horizon_gossip_runs_steps_not_zero(self):
        """Pre-fix: steps < record_every silently ran ZERO steps and
        returned an empty history."""
        g = ring_graph(8)
        rng = np.random.default_rng(1)
        sol = rng.standard_normal((8, 2)).astype(np.float32)
        c = np.ones(8, np.float32)
        short = sparse_async_gossip(SparseTopology.from_graph(g), sol, c,
                                    0.8, steps=5, seed=0, record_every=100)
        explicit = sparse_async_gossip(SparseTopology.from_graph(g), sol, c,
                                       0.8, steps=5, seed=0, record_every=5)
        assert short.theta_hist.shape[0] == 1
        np.testing.assert_array_equal(short.theta_hist, explicit.theta_hist)
        assert not np.array_equal(short.theta_hist[-1], sol)   # it DID run
        dense = async_gossip(g, sol, c, 0.8, steps=5, seed=0,
                             record_every=100)
        np.testing.assert_array_equal(dense.theta_hist, short.theta_hist)

    def test_short_horizon_admm_does_not_overrun(self):
        """Pre-fix: ``max(1, steps // record_every)`` ran a full oversized
        chunk — 50 ticks for a 5-step request."""
        n = 8
        rng = np.random.default_rng(2)
        pts = rng.standard_normal((n, 2)) * 0.5
        g = gaussian_kernel_graph(pts, sigma=1.0)
        xs = [rng.standard_normal((int(rng.integers(1, 6)), 1))
              for _ in range(n)]
        data = pad_datasets(xs, [np.zeros(len(x)) for x in xs])
        sol = solitary_mean(data)
        topo = SparseTopology.from_graph(g)
        short = sparse_async_admm(topo, data, 0.1, 1.0, steps=5, seed=0,
                                  record_every=50, theta_sol=sol)
        explicit = sparse_async_admm(topo, data, 0.1, 1.0, steps=5, seed=0,
                                     record_every=5, theta_sol=sol)
        np.testing.assert_array_equal(short.theta_hist, explicit.theta_hist)
        dense = async_admm(g, data, 0.1, 1.0, "quadratic", steps=5, seed=0,
                           record_every=50, theta_sol=sol)
        np.testing.assert_array_equal(dense.theta_hist, short.theta_hist)

    def test_non_divisible_steps_floored(self):
        g = ring_graph(8)
        rng = np.random.default_rng(3)
        sol = rng.standard_normal((8, 2)).astype(np.float32)
        c = np.ones(8, np.float32)
        a = sparse_async_gossip(SparseTopology.from_graph(g), sol, c, 0.8,
                                steps=17, seed=0, record_every=5)
        b = sparse_async_gossip(SparseTopology.from_graph(g), sol, c, 0.8,
                                steps=15, seed=0, record_every=5)
        assert a.theta_hist.shape[0] == 3
        np.testing.assert_array_equal(a.theta_hist, b.theta_hist)


class TestAccountingInvariant:
    """delivered + dropped == 2 * (events - invalid) for ``run_mp_scenario``
    across every NetworkConditions field (satellite: test each in
    isolation; invalid == 0 whenever the network never empties)."""

    FIELD_CONDITIONS = {
        "clean": NetworkConditions(),
        "drop": NetworkConditions(drop_prob=0.3),
        "stale": NetworkConditions(stale_prob=0.5),
        "straggler": NetworkConditions(straggler_frac=0.4,
                                       straggler_factor=0.05),
        "churn": NetworkConditions(churn_rate=0.02),
        "partition": NetworkConditions(partition_start=5, partition_end=25),
        "all": NetworkConditions(drop_prob=0.15, stale_prob=0.2,
                                 straggler_frac=0.3, straggler_factor=0.1,
                                 churn_rate=0.02, partition_start=5,
                                 partition_end=25),
    }

    @pytest.mark.parametrize("name", sorted(FIELD_CONDITIONS))
    def test_invariant(self, name):
        cond = self.FIELD_CONDITIONS[name]
        topo = random_geometric_topology(150, k=4, seed=0)
        rng = np.random.default_rng(0)
        sol = rng.standard_normal((150, 3)).astype(np.float32)
        c = rng.uniform(0.05, 1.0, 150).astype(np.float32)
        tr = run_mp_scenario(topo, sol, c, 0.9, cond, rounds=40, batch=32,
                             seed=7, record_every=10)
        assert tr.delivered + tr.dropped == 2 * (tr.events - tr.invalid)
        assert tr.invalid == 0          # 150 agents never all churn out
        if name == "clean":
            assert tr.dropped == 0 and tr.delivered == 2 * tr.events
        if name in ("drop", "partition", "all"):
            assert tr.dropped > 0


class TestPerOpSlotTable:
    """The per-op MP round (``backend=None``) carries its slot table flat,
    ``(n * k_max, p)`` with row ``i * k_max + s`` for agent i's slot s; its
    counters, snapshots and telemetry are bit for bit those of the
    ``(n, k_max, p)`` layout it replaced, recorded in
    ``tests/data/mp_lossy10_n3000.npz``.  Under lossy-10 at B = n / 2
    every round lands 100-160 delivered messages on a (row, slot) that
    another message of the batch also targets (k_max = 15 here), so the
    scatters' last-write-wins order is held to the record too."""

    RECORD = pathlib.Path(__file__).parent / "data" / "mp_lossy10_n3000.npz"

    @staticmethod
    def run(tel):
        n, p, rounds = 3000, 16, 40
        topo = random_geometric_topology(n, k=8, seed=0)
        rng = np.random.default_rng(0)
        sol = rng.standard_normal((n, p)).astype(np.float32)
        c = rng.uniform(0.05, 1.0, n).astype(np.float32)
        cond = get_scenario("lossy-10").make_conditions(rounds)
        return run_mp_scenario(
            topo, sol, c, 0.8, cond, rounds=rounds, batch=1500, seed=11,
            record_every=20,
            telemetry=TelemetryConfig(enabled=True) if tel else None)

    @pytest.mark.parametrize("tel", [False, True], ids=["plain", "telemetry"])
    def test_matches_recorded_3d_layout(self, tel):
        want = np.load(self.RECORD)
        tr = self.run(tel)
        assert [tr.delivered, tr.dropped, tr.invalid, tr.events] \
            == want["counters"].tolist()
        np.testing.assert_array_equal(tr.theta_hist, want["theta_hist"])
        np.testing.assert_array_equal(tr.active_hist, want["active_hist"])
        if tel:
            for name in ("objective", "staleness", "updates"):
                np.testing.assert_array_equal(
                    getattr(tr.telemetry, name), want[name])
        else:
            assert tr.telemetry is None


# ---------------------------------------------------------------------------
# sparse gather-mix kernel
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("n,k,p", [(64, 4, 32), (100, 7, 40), (130, 2, 16)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_sparse_gather_mix_matches_ref(n, k, p, dtype):
    rng = np.random.default_rng(n + k)
    idx = jnp.asarray(rng.integers(0, n, (n, k)), jnp.int32)
    w = rng.uniform(0, 1, (n, k)).astype(np.float32)
    w[:, -1] = 0.0                                  # a pad column
    w = jnp.asarray(w)
    b = jnp.asarray(rng.uniform(0, 1, n), jnp.float32)
    table = jnp.asarray(rng.standard_normal((n, p)), dtype)
    sol = jnp.asarray(rng.standard_normal((n, p)), dtype)
    got = ops.sparse_gather_mix(table, idx, w, b, sol, interpret=True)
    want = ref.sparse_gather_mix(table, idx, w, b, sol)
    tol = dict(atol=2e-2, rtol=2e-2) if dtype == jnp.bfloat16 else \
        dict(atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **tol)


def test_sync_sweep_kernel_path_matches_jnp_path(monkeypatch):
    # use_kernel requests the Pallas impl; off-TPU it runs only with the
    # explicit interpret opt-in
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "1")
    topo = random_geometric_topology(200, k=5, seed=1)
    rng = np.random.default_rng(4)
    sol = rng.standard_normal((200, 8)).astype(np.float32)
    c = rng.uniform(0.05, 1.0, 200).astype(np.float32)
    a = np.asarray(sparse_sync_mp(topo, sol, c, 0.9, sweeps=20))
    b = np.asarray(sparse_sync_mp(topo, sol, c, 0.9, sweeps=20,
                                  use_kernel=True))
    np.testing.assert_allclose(a, b, atol=1e-5)
