"""Partitioned multi-device simulation: greedy edge-cut partitioner, shard/
halo layout invariants, sharded-engine parity with the single-device sparse
engine (in-process on however many devices exist, plus an 8-fake-device
subprocess), and partition edge cases (isolated agents, zero cross-edge
shards, n not divisible by P, fixed-seed determinism)."""

import math
import os
import subprocess
import sys
import textwrap

import jax
import numpy as np
import pytest

from repro.core.losses import pad_datasets, solitary_mean
from repro.core.sparse import tables_from_adjacency
from repro.simulate import (GraphPartition, NetworkConditions, SparseTopology,
                            block_partition, cluster_topology,
                            default_local_batch, default_local_events,
                            edge_cut, greedy_partition,
                            precompute_event_stream,
                            random_geometric_topology, ring_topology,
                            run_cl_scenario, run_cl_scenario_sharded,
                            run_mp_scenario, run_mp_scenario_sharded,
                            stream_totals)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def two_component_topology(half: int = 20) -> SparseTopology:
    """Two disjoint rings — a partition of it can have zero cross-edges."""
    nbrs, wts = [], []
    for comp in range(2):
        lo = comp * half
        for v in range(half):
            a, b = lo + (v - 1) % half, lo + (v + 1) % half
            nbrs.append(np.sort(np.unique([a, b])))
            wts.append(np.ones(len(nbrs[-1])))
    tabs = tables_from_adjacency(nbrs, wts)
    groups = (np.arange(2 * half) >= half).astype(np.int32)
    return SparseTopology(tabs, groups)


# ---------------------------------------------------------------------------
# greedy partitioner
# ---------------------------------------------------------------------------


class TestGreedyPartition:
    def test_balanced_and_complete(self):
        topo = random_geometric_topology(501, k=5, seed=0)   # n % P != 0
        a = greedy_partition(topo, 4)
        assert a.shape == (501,) and a.min() >= 0 and a.max() < 4
        cap = math.ceil(501 / 4)
        assert np.bincount(a, minlength=4).max() <= cap + max(1, cap // 16)

    def test_deterministic_under_fixed_seed(self):
        topo = random_geometric_topology(300, k=4, seed=1)
        a1 = greedy_partition(topo, 8, seed=7)
        a2 = greedy_partition(topo, 8, seed=7)
        assert np.array_equal(a1, a2)

    def test_beats_random_assignment(self):
        topo = random_geometric_topology(1000, k=6, seed=0)
        rng = np.random.default_rng(0)
        rand = rng.integers(0, 8, topo.n).astype(np.int32)
        assert edge_cut(topo, greedy_partition(topo, 8)) \
            < 0.5 * edge_cut(topo, rand)

    def test_recovers_cluster_structure(self):
        topo = cluster_topology(400, n_clusters=4, k_intra=4, bridges=2,
                                seed=0)
        cut = edge_cut(topo, greedy_partition(topo, 4, refine_passes=8))
        # clusters are contiguous ids, so block partition is near-optimal;
        # greedy must land in its ballpark, not at random-cut levels
        assert cut <= 4 * max(1, edge_cut(topo, block_partition(topo, 4)))

    def test_single_shard_is_trivial(self):
        topo = ring_topology(32)
        assert np.array_equal(greedy_partition(topo, 1), np.zeros(32))
        assert edge_cut(topo, greedy_partition(topo, 1)) == 0

    def test_isolated_agents_rejected_by_generators(self):
        """The topology layer guarantees no isolated agents, which the
        partition layout relies on (every halo id has a boundary source)."""
        with pytest.raises(ValueError, match="at least one neighbor"):
            tables_from_adjacency([np.array([1]), np.array([0]),
                                   np.array([], np.int64)],
                                  [np.ones(1), np.ones(1), np.ones(0)])


# ---------------------------------------------------------------------------
# shard/halo layout
# ---------------------------------------------------------------------------


class TestGraphPartitionLayout:
    def _check_layout(self, topo, part):
        tabs = topo.tables
        n, m, H = part.n, part.shard_size, part.halo_size
        # perm_slot inverts local placement
        assert np.array_equal(
            part.local_ids.reshape(-1)[part.perm_slot], np.arange(n))
        # every neighbor of a local agent is fetchable (local or halo)
        for q in range(part.n_shards):
            fetch = part.fetch[q]
            for v in np.where(part.owner == q)[0]:
                for u in tabs.nbr_idx[v, :tabs.deg_count[v]]:
                    assert fetch[u] < m + H, (q, v, u)
        # halo reconstruction: gathering boundary buffers lands each halo
        # agent's value at its fetch slot
        vals = np.arange(n, dtype=np.float32)[:, None]
        loc = part.shard_rows(vals).reshape(part.n_shards, m, 1)
        bufs = np.stack([loc[q, part.bnd_pos[q]]
                         for q in range(part.n_shards)])  # (P, B, 1)
        for q in range(part.n_shards):
            halo = bufs[part.halo_src_shard[q], part.halo_src_pos[q]]
            ext = np.concatenate([loc[q], halo, np.zeros((1, 1))])
            for a in range(n):
                if part.fetch[q, a] < m + H:
                    assert ext[part.fetch[q, a], 0] == a

    def test_layout_roundtrip(self):
        topo = random_geometric_topology(230, k=5, seed=2)   # 230 % 8 != 0
        part = GraphPartition.build(topo, greedy_partition(topo, 8), 8)
        assert part.shard_size * 8 >= 230
        self._check_layout(topo, part)

    def test_zero_cross_edge_shard(self):
        """Disjoint components on separate shards: no cut, no halo."""
        topo = two_component_topology(20)
        part = GraphPartition.build(topo, topo.groups, 2)
        assert part.edge_cut == 0
        assert part.halo_size == 0 and part.boundary_size == 0
        self._check_layout(topo, part)

    def test_unshard_inverts_shard(self):
        topo = random_geometric_topology(100, k=4, seed=3)
        part = GraphPartition.build(topo, greedy_partition(topo, 4), 4)
        x = np.random.default_rng(0).standard_normal((100, 7)) \
            .astype(np.float32)
        assert np.array_equal(part.unshard_rows(part.shard_rows(x)), x)

    def test_layout_reused_for_the_same_topology_and_assignment(self):
        """A caller making many calls over one topology and assignment
        builds the layout once; another topology object rebuilds it."""
        from repro.simulate.partition import _sharded_setup
        topo = random_geometric_topology(120, k=4, seed=5)
        a = greedy_partition(topo, 1)
        part = _sharded_setup(topo, 1, None, a, 0)[3]
        assert _sharded_setup(topo, 1, None, a.copy(), 0)[3] is part
        other = random_geometric_topology(120, k=4, seed=5)
        assert _sharded_setup(other, 1, None, a, 0)[3] is not part

    def test_capacity_heuristics(self):
        assert default_local_batch(100, 1) == 200
        assert default_local_events(100, 1) == 100
        for P in (2, 4, 8):
            assert 2 * 100 // P < default_local_batch(100, P) <= 200
            assert default_local_events(100, P) <= 100


# ---------------------------------------------------------------------------
# event-stream replay + sharded engine parity (in-process device count)
# ---------------------------------------------------------------------------


CONDITIONS = {
    "clean": NetworkConditions(),
    "faulty": NetworkConditions(drop_prob=0.1, stale_prob=0.3,
                                churn_rate=0.01, straggler_frac=0.3,
                                partition_start=10, partition_end=30),
}


class TestShardedParity:
    @pytest.fixture(scope="class")
    def problem(self):
        topo = random_geometric_topology(300, k=5, seed=0)
        rng = np.random.default_rng(0)
        sol = rng.standard_normal((300, 4)).astype(np.float32)
        c = rng.uniform(0.05, 1.0, 300).astype(np.float32)
        return topo, sol, c

    def test_event_stream_totals(self, problem):
        topo, sol, c = problem
        cond = CONDITIONS["faulty"]
        stream = precompute_event_stream(
            topo.device_tables(), np.asarray(topo.partition_halves()),
            cond, 32, 5, 60)
        tr = run_mp_scenario(topo, sol, c, 0.9, cond, rounds=60, batch=32,
                             seed=5, record_every=60)
        delivered = int(np.asarray(stream.deliver_ij).sum()
                        + np.asarray(stream.deliver_ji).sum())
        assert delivered == tr.delivered
        assert 2 * 60 * 32 - delivered == tr.dropped

    @pytest.mark.parametrize("name", sorted(CONDITIONS))
    def test_matches_single_device(self, problem, name):
        """The tentpole acceptance: identical trajectory, counters, and
        activity history on whatever mesh this process has (1 device in the
        fast lane; 8 in the multi-device CI job)."""
        topo, sol, c = problem
        cond = CONDITIONS[name]
        tr = run_mp_scenario(topo, sol, c, 0.9, cond, rounds=60, batch=48,
                             seed=3, record_every=20)
        sh = run_mp_scenario_sharded(topo, sol, c, 0.9, cond, rounds=60,
                                     batch=48, seed=3, record_every=20)
        assert sh.overflow == 0
        assert sh.n_shards == jax.device_count()
        np.testing.assert_allclose(sh.theta_hist, tr.theta_hist, atol=1e-5)
        np.testing.assert_allclose(sh.active_hist, tr.active_hist)
        assert (sh.delivered, sh.dropped, sh.rounds, sh.events) \
            == (tr.delivered, tr.dropped, tr.rounds, tr.events)

    def test_ring_exchange_matches(self, problem):
        topo, sol, c = problem
        cond = CONDITIONS["faulty"]
        a = run_mp_scenario_sharded(topo, sol, c, 0.9, cond, rounds=40,
                                    batch=32, seed=1, record_every=20)
        b = run_mp_scenario_sharded(topo, sol, c, 0.9, cond, rounds=40,
                                    batch=32, seed=1, record_every=20,
                                    exchange="ring")
        assert np.array_equal(a.theta_hist, b.theta_hist)

    def test_overflow_counted_not_crashed(self, problem):
        """A deliberately tiny update buffer must degrade by *counting*
        dropped updates, never by crashing or silently diverging."""
        topo, sol, c = problem
        tr = run_mp_scenario_sharded(topo, sol, c, 0.9, CONDITIONS["clean"],
                                     rounds=20, batch=64, seed=0,
                                     record_every=20, local_batch=1)
        if jax.device_count() == 1:
            assert tr.overflow > 0          # U = 1 cannot hold 2B updates
        assert np.isfinite(tr.theta_hist).all()

    def test_assignment_exceeding_mesh_raises(self, problem):
        topo, sol, c = problem
        bad = np.arange(topo.n, dtype=np.int32) % (jax.device_count() + 3)
        with pytest.raises(ValueError, match="mesh"):
            run_mp_scenario_sharded(topo, sol, c, 0.9, CONDITIONS["clean"],
                                    rounds=10, batch=8, assignment=bad)

    def test_mesh_larger_than_device_count_raises(self):
        """Asking for more shards than devices is an error, never a
        silently smaller mesh."""
        from repro.launch.sim_mesh import make_sim_mesh, mesh_shards
        with pytest.raises(ValueError, match="device"):
            make_sim_mesh(jax.device_count() + 1)
        with pytest.raises(ValueError, match="device"):
            make_sim_mesh(0)
        assert mesh_shards(make_sim_mesh()) == jax.device_count()

    def test_replay_parity_churn_and_partition_together(self, problem):
        """EventStream replay with churn AND a partition window active at
        once: the materialized stream and the inline engine agree on every
        counter and on the trajectory replayed from it."""
        topo, sol, c = problem
        cond = NetworkConditions(churn_rate=0.03, partition_start=5,
                                 partition_end=25)
        tr = run_mp_scenario(topo, sol, c, 0.9, cond, rounds=40, batch=32,
                             seed=11, record_every=10)
        stream = precompute_event_stream(
            topo.device_tables(), np.asarray(topo.partition_halves()),
            cond, 32, 11, 40)
        delivered, dropped, invalid = stream_totals(stream)
        assert (delivered, dropped, invalid) \
            == (tr.delivered, tr.dropped, tr.invalid)
        assert tr.delivered + tr.dropped == 2 * (tr.events - tr.invalid)
        sh = run_mp_scenario_sharded(topo, sol, c, 0.9, cond, rounds=40,
                                     batch=32, seed=11, record_every=10)
        assert sh.overflow == 0
        np.testing.assert_allclose(sh.theta_hist, tr.theta_hist, atol=1e-5)
        np.testing.assert_allclose(sh.active_hist, tr.active_hist)


# ---------------------------------------------------------------------------
# sharded CL-ADMM parity (in-process device count; 8 devices in the
# multi-device CI lane and in the subprocess below)
# ---------------------------------------------------------------------------


class TestShardedCLParity:
    @pytest.fixture(scope="class")
    def problem(self):
        topo = random_geometric_topology(300, k=5, seed=0)
        rng = np.random.default_rng(0)
        xs = [rng.standard_normal((int(rng.integers(1, 8)), 4))
              for _ in range(300)]
        data = pad_datasets(xs, [np.zeros(len(x)) for x in xs])
        sol = np.asarray(solitary_mean(data), np.float32)
        return topo, data, sol

    @pytest.mark.parametrize("name", sorted(CONDITIONS))
    def test_matches_single_device_bitwise(self, problem, name):
        """Tentpole acceptance: the sharded CL-ADMM trajectory is
        bit-for-bit the single-device one (maxerr 0.0) with zero buffer
        overflow, on whatever mesh this process has."""
        topo, data, sol = problem
        cond = CONDITIONS[name]
        tr = run_cl_scenario(topo, data, 0.1, 1.0, cond, rounds=60,
                             batch=48, seed=3, record_every=20,
                             theta_sol=sol)
        sh = run_cl_scenario_sharded(topo, data, 0.1, 1.0, cond, rounds=60,
                                     batch=48, seed=3, record_every=20,
                                     theta_sol=sol)
        assert sh.overflow == 0
        assert sh.n_shards == jax.device_count()
        assert np.abs(sh.theta_hist - tr.theta_hist).max() == 0.0
        np.testing.assert_allclose(sh.active_hist, tr.active_hist)
        assert (sh.delivered, sh.dropped, sh.invalid, sh.rounds, sh.events) \
            == (tr.delivered, tr.dropped, tr.invalid, tr.rounds, tr.events)

    def test_ring_exchange_matches(self, problem):
        topo, data, sol = problem
        kw = dict(rounds=40, batch=32, seed=1, record_every=20,
                  theta_sol=sol)
        a = run_cl_scenario_sharded(topo, data, 0.1, 1.0,
                                    CONDITIONS["faulty"], **kw)
        b = run_cl_scenario_sharded(topo, data, 0.1, 1.0,
                                    CONDITIONS["faulty"], exchange="ring",
                                    **kw)
        assert np.array_equal(a.theta_hist, b.theta_hist)

    def test_overflow_counted_not_crashed(self, problem):
        topo, data, sol = problem
        tr = run_cl_scenario_sharded(topo, data, 0.1, 1.0,
                                     CONDITIONS["clean"], rounds=20,
                                     batch=64, seed=0, record_every=20,
                                     local_batch=1, theta_sol=sol)
        if jax.device_count() == 1:
            assert tr.overflow > 0          # U = 1 cannot hold 2B updates
        assert np.isfinite(tr.theta_hist).all()


# ---------------------------------------------------------------------------
# 8-fake-device subprocess: true multi-shard execution wherever the suite
# runs (the in-process tests above only see this host's device count)
# ---------------------------------------------------------------------------


SUBPROC = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import numpy as np
    import jax
    assert jax.device_count() == 8
    from repro.kernels.dispatch import ReproBackend
    from repro.simulate import (NetworkConditions, cluster_topology,
                                random_geometric_topology, run_mp_scenario,
                                run_mp_scenario_sharded, sparse_sync_mp)
    import test_partition as tp

    # n = 203 is not divisible by 8; faulty conditions hit every code path
    topo = random_geometric_topology(203, k=5, seed=0)
    rng = np.random.default_rng(0)
    sol = rng.standard_normal((203, 4)).astype(np.float32)
    c = rng.uniform(0.05, 1.0, 203).astype(np.float32)
    cond = NetworkConditions(drop_prob=0.1, stale_prob=0.3, churn_rate=0.01,
                             straggler_frac=0.3, partition_start=5,
                             partition_end=20)
    tr = run_mp_scenario(topo, sol, c, 0.9, cond, rounds=40, batch=32,
                         seed=3, record_every=10)
    for exchange in ("all_gather", "ring"):
        sh = run_mp_scenario_sharded(topo, sol, c, 0.9, cond, rounds=40,
                                     batch=32, seed=3, record_every=10,
                                     exchange=exchange)
        assert sh.n_shards == 8 and sh.overflow == 0, exchange
        assert np.abs(sh.theta_hist - tr.theta_hist).max() <= 1e-5, exchange

    # zero cross-edge shards: two disjoint components, explicit assignment
    topo2 = tp.two_component_topology(20)
    sol2 = rng.standard_normal((40, 3)).astype(np.float32)
    c2 = rng.uniform(0.1, 1.0, 40).astype(np.float32)
    tr2 = run_mp_scenario(topo2, sol2, c2, 0.8, NetworkConditions(),
                          rounds=30, batch=8, seed=1, record_every=10)
    sh2 = run_mp_scenario_sharded(topo2, sol2, c2, 0.8, NetworkConditions(),
                                  rounds=30, batch=8, seed=1,
                                  record_every=10,
                                  assignment=topo2.groups, n_shards=2)
    assert sh2.edge_cut == 0 and sh2.halo_size == 0
    assert np.array_equal(sh2.theta_hist, tr2.theta_hist)

    # sharded dispatch impls drive the sync sweep across all 8 devices
    topo3 = random_geometric_topology(300, k=5, seed=1)
    sol3 = rng.standard_normal((300, 8)).astype(np.float32)
    c3 = rng.uniform(0.05, 1.0, 300).astype(np.float32)
    want = np.asarray(sparse_sync_mp(topo3, sol3, c3, 0.9, sweeps=15))
    got = np.asarray(sparse_sync_mp(
        topo3, sol3, c3, 0.9, sweeps=15,
        backend=ReproBackend.using(sparse_mix="xla_sharded")))
    assert np.abs(got - want).max() <= 1e-5

    # sharded ADMM primal/edge dispatch impls match their row-wise forms
    import jax.numpy as jnp
    from repro.kernels.dispatch import resolve
    k_, p_ = 6, 16
    w_ = jnp.asarray(rng.uniform(0.1, 1, (40, k_)), jnp.float32)
    lv = jnp.asarray(rng.uniform(size=(40, k_)) < 0.8)
    zo, zn, lo, ln = (jnp.asarray(rng.standard_normal((40, k_, p_)),
                                  jnp.float32) for _ in range(4))
    D_ = jnp.asarray(rng.uniform(1, 4, 40), jnp.float32)
    m_ = jnp.asarray(rng.integers(1, 20, 40), jnp.float32)
    sx_ = jnp.asarray(rng.standard_normal((40, p_)), jnp.float32)
    xla = resolve("admm_primal", ReproBackend.using(admm_primal="xla"))
    shd = resolve("admm_primal",
                  ReproBackend.using(admm_primal="xla_sharded"))
    want_t, want_js = jax.vmap(
        lambda *a: xla(*a, 0.05, 1.0))(w_, lv, zo, zn, lo, ln, D_, m_, sx_)
    got_t, got_js = shd(w_, lv, zo, zn, lo, ln, D_, m_, sx_, 0.05, 1.0)
    assert np.abs(np.asarray(got_t) - np.asarray(want_t)).max() <= 1e-5
    assert np.abs(np.asarray(got_js) - np.asarray(want_js)).max() <= 1e-5
    e_args = tuple(jnp.asarray(rng.standard_normal((40, p_)), jnp.float32)
                   for _ in range(8))
    ref_e = resolve("admm_edge", ReproBackend.using(admm_edge="reference"))
    shd_e = resolve("admm_edge", ReproBackend.using(admm_edge="xla_sharded"))
    for a, b in zip(ref_e(*e_args, rho=1.5), shd_e(*e_args, rho=1.5)):
        assert np.abs(np.asarray(a) - np.asarray(b)).max() <= 1e-5

    # CL-ADMM sharded parity across the 8-shard mesh (faulty conditions)
    from repro.core.losses import pad_datasets, solitary_mean
    from repro.simulate import run_cl_scenario, run_cl_scenario_sharded
    xs = [rng.standard_normal((int(rng.integers(1, 8)), 4))
          for _ in range(203)]
    data = pad_datasets(xs, [np.zeros(len(x)) for x in xs])
    soln = np.asarray(solitary_mean(data), np.float32)
    cl = run_cl_scenario(topo, data, 0.1, 1.0, cond, rounds=40, batch=32,
                         seed=3, record_every=10, theta_sol=soln)
    for exchange in ("all_gather", "ring"):
        sh_cl = run_cl_scenario_sharded(topo, data, 0.1, 1.0, cond,
                                        rounds=40, batch=32, seed=3,
                                        record_every=10, theta_sol=soln,
                                        exchange=exchange)
        assert sh_cl.n_shards == 8 and sh_cl.overflow == 0, exchange
        assert np.abs(sh_cl.theta_hist - cl.theta_hist).max() == 0.0, exchange

    # acceptance run: n = 10k agents, 8 shards, bit-for-bit, zero overflow
    topo4 = random_geometric_topology(10000, k=6, seed=2)
    m4 = 3
    x4 = rng.standard_normal((10000, m4, 4)).astype(np.float32)
    data4 = pad_datasets(list(x4), [np.zeros(m4)] * 10000)
    sol4 = np.asarray(solitary_mean(data4), np.float32)
    cond4 = NetworkConditions(drop_prob=0.1, stale_prob=0.2,
                              churn_rate=0.005, straggler_frac=0.2,
                              partition_start=4, partition_end=12)
    cl4 = run_cl_scenario(topo4, data4, 0.1, 1.0, cond4, rounds=24,
                          batch=1000, seed=0, record_every=8,
                          theta_sol=sol4)
    sh4 = run_cl_scenario_sharded(topo4, data4, 0.1, 1.0, cond4, rounds=24,
                                  batch=1000, seed=0, record_every=8,
                                  theta_sol=sol4)
    assert sh4.n_shards == 8 and sh4.overflow == 0
    assert np.abs(sh4.theta_hist - cl4.theta_hist).max() == 0.0

    # the on-device warm start (each shard gathers its neighbour copies
    # from one exchange of the models) is the single-device one, bit for
    # bit, under churn alone and under loss with staleness
    for cond5 in (NetworkConditions(churn_rate=0.02),
                  NetworkConditions(drop_prob=0.2, stale_prob=0.4)):
        cl5 = run_cl_scenario(topo, data, 0.1, 1.0, cond5, rounds=30,
                              batch=32, seed=7, record_every=10,
                              theta_sol=soln)
        sh5 = run_cl_scenario_sharded(topo, data, 0.1, 1.0, cond5,
                                      rounds=30, batch=32, seed=7,
                                      record_every=10, theta_sol=soln)
        assert sh5.overflow == 0, cond5
        assert np.array_equal(sh5.theta_hist, cl5.theta_hist), cond5

    # an isolated agent: its row of copies (never read) may differ from
    # the single-device one, and the trajectory does not
    from repro.core.sparse import tables_from_adjacency
    from repro.simulate import SparseTopology
    ring = [v for v in range(60) if v % 15 != 7]    # 7, 22, 37, 52 alone
    rows = [np.array([], np.int64)] * 60
    for a, v in enumerate(ring):
        rows[v] = np.sort([ring[a - 1], ring[(a + 1) % len(ring)]])
    topo6 = SparseTopology(
        tables_from_adjacency(rows, [np.ones(len(r)) for r in rows],
                              allow_isolated=True),
        (np.arange(60) >= 30).astype(np.int32))
    xs6 = [rng.standard_normal((3, 4)) for _ in range(60)]
    data6 = pad_datasets(xs6, [np.zeros(3)] * 60)
    sol6 = np.asarray(solitary_mean(data6), np.float32)
    kw6 = dict(rounds=20, batch=10, seed=2, record_every=10,
               theta_sol=sol6)
    cl6 = run_cl_scenario(topo6, data6, 0.1, 1.0, cond, **kw6)
    sh6 = run_cl_scenario_sharded(topo6, data6, 0.1, 1.0, cond,
                                  n_shards=4, **kw6)
    assert sh6.overflow == 0 and sh6.halo_size > 0
    assert np.array_equal(sh6.theta_hist, cl6.theta_hist)

    # under the profiler: a sharded CL call uploads the tables, the host
    # inputs and the partition's tables, and no (n, k_max, p) array of
    # neighbour copies; its scan span states the partition's sizes
    import glob, gzip, json, tempfile
    from repro.core.losses import AgentData
    from repro.launch.sim_mesh import halo_payload_bytes
    from repro.simulate import GraphPartition, ScenarioSpec, greedy_partition
    from repro.simulate import run_scenario
    host = AgentData(*(np.asarray(a) for a in (data.x, data.y, data.mask)))
    trace_dir = tempfile.mkdtemp()
    jax.profiler.start_trace(trace_dir)
    run_scenario(ScenarioSpec(
        algo="cl", topology=topo, data=host, mu=0.1, rho=1.0,
        theta_sol=soln, conditions=cond, rounds=10, batch=16, seed=1,
        record_every=5, sharded=True))
    jax.profiler.stop_trace()
    path, = glob.glob(trace_dir + "/plugins/profile/*/*.trace.json.gz")
    with gzip.open(path, "rt") as f:
        ev = {e["name"]: e.get("args", {}) for e in json.load(f)["traceEvents"]
              if e.get("ph") == "X" and e["name"].startswith("repro/host/")}
    part = GraphPartition.build(topo, greedy_partition(topo, 8), 8)
    n, k, p = topo.n, topo.k_max, soln.shape[1]
    Pm = 8 * part.shard_size
    tabs = [np.asarray(a) for a in topo.tables]
    up = sum(a.size * jax.dtypes.canonicalize_dtype(a.dtype).itemsize
             for a in tabs + [topo.partition_halves(), soln,
                                       np.asarray(host.x, np.float32),
                                       np.asarray(host.mask, np.float32),
                                       part.local_ids, part.fetch,
                                       part.bnd_pos, part.halo_src_shard,
                                       part.halo_src_pos])
    up += 4 * (2 * Pm * k + 2 * Pm)     # the shard-stacked (Pm, k) and (Pm,)
    assert int(ev["repro/host/prepare"]["bytes"]) == up, (ev, up)
    args = {a: int(v) for a, v in ev["repro/host/scan"].items()}
    assert args == dict(
        shards=8, boundary_rows=part.boundary_size,
        halo_rows=part.halo_size, edge_cut=part.edge_cut,
        halo_bytes_per_round=halo_payload_bytes(
            8, part.boundary_size, 4 * (1 + 3 * k) * p, part.halo_size))
    print("SHARDED-8DEV-OK")
""")


def test_eight_device_parity_subprocess():
    """Full 8-shard execution in a subprocess (the XLA device-count flag
    must precede jax init, which pytest has already done here)."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src") + os.pathsep \
        + os.path.dirname(__file__) \
        + os.pathsep + env.get("PYTHONPATH", "")
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", SUBPROC], env=env,
                         capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stdout + out.stderr
    assert "SHARDED-8DEV-OK" in out.stdout
