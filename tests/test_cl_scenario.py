"""Batched-event CL-ADMM scenario engine (``run_cl_scenario``): parity with
the exact one-event-per-tick engine on an identical schedule, fault-model
behavior (drops, staleness, churn, partitions), accounting invariants, the
shared recording policy, and the round's payload carry (bit-for-bit with
whole-state publish snapshots, and free of whole-state copies)."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.losses import pad_datasets, solitary_mean
from repro.core.sparse import (batched_admm_primal, record_chunks,
                               sample_event, tables_from_adjacency)
from repro.simulate import (EventStream, NetworkConditions, SparseADMMState,
                            SparseTopology, init_sparse_admm,
                            precompute_event_stream,
                            random_geometric_topology, run_cl_scenario,
                            sparse_async_admm)
from repro.simulate import engines
from repro.telemetry.config import TelemetryConfig


def exact_admm_stream(topo, steps, record_every, seed) -> EventStream:
    """Replay ``sparse_async_admm``'s exact tick schedule as a B = 1 stream:
    same PRNG key tree (split per record chunk, then per tick), all
    deliveries clean."""
    tabs = topo.device_tables()
    n = topo.n
    re_, n_rec = record_chunks(steps, record_every)
    keys = jax.random.split(jax.random.PRNGKey(seed), n_rec)
    tick_keys = jnp.concatenate([jax.random.split(k, re_) for k in keys])
    i, s = jax.vmap(lambda k: sample_event(k, n, tabs.slot_cdf,
                                           tabs.deg_count))(tick_keys)
    i = np.asarray(i)[:, None]
    s = np.asarray(s)[:, None]
    j = np.asarray(tabs.nbr_idx)[i, s]
    r = np.asarray(tabs.rev_slot)[i, s]
    t = np.ones(i.shape, bool)
    return EventStream(i, s, j, r, t, t, ~t, ~t, t, ~t, ~t,
                       np.ones(i.shape[0], np.float32))


@pytest.fixture(scope="module")
def problem():
    topo = random_geometric_topology(150, k=4, seed=0)
    rng = np.random.default_rng(0)
    xs = [rng.standard_normal((int(rng.integers(1, 8)), 3))
          for _ in range(150)]
    data = pad_datasets(xs, [np.zeros(len(x)) for x in xs])
    sol = np.asarray(solitary_mean(data), np.float32)
    return topo, data, sol


class TestExactScheduleParity:
    def test_matches_sparse_async_admm(self, problem):
        """Tentpole acceptance: with all-default NetworkConditions and the
        exact engine's event schedule, the batched engine reproduces
        ``sparse_async_admm``'s trajectory (to f32 rounding — the batched
        phases vmap the identical per-row primal/edge expressions)."""
        topo, data, sol = problem
        steps, re_ = 300, 50
        stream = exact_admm_stream(topo, steps, re_, seed=5)
        exact = sparse_async_admm(topo, data, 0.1, 1.0, steps=steps, seed=5,
                                  record_every=re_, theta_sol=sol)
        batched = run_cl_scenario(topo, data, 0.1, 1.0, NetworkConditions(),
                                  rounds=steps, batch=1, seed=5,
                                  record_every=re_, theta_sol=sol,
                                  stream=stream)
        assert batched.theta_hist.shape == exact.theta_hist.shape
        np.testing.assert_allclose(batched.theta_hist, exact.theta_hist,
                                   atol=1e-5, rtol=1e-5)
        # full edge state agrees too, not just the self models
        for a, b in [(batched.final.theta, exact.final.theta),
                     (batched.final.K, exact.final.K),
                     (batched.final.Z_own, exact.final.Z_own),
                     (batched.final.Z_nbr, exact.final.Z_nbr),
                     (batched.final.L_own, exact.final.L_own),
                     (batched.final.L_nbr, exact.final.L_nbr)]:
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=1e-5, rtol=1e-5)

    def test_stream_shape_mismatch_raises(self, problem):
        topo, data, sol = problem
        stream = exact_admm_stream(topo, 20, 10, seed=0)
        with pytest.raises(ValueError, match="rounds"):
            run_cl_scenario(topo, data, 0.1, 1.0, NetworkConditions(),
                            rounds=40, batch=1, record_every=10,
                            theta_sol=sol, stream=stream)


class TestCLScenarioFaults:
    def test_clean_counters_and_convergence(self, problem):
        topo, data, sol = problem
        tr = run_cl_scenario(topo, data, 0.1, 1.0, NetworkConditions(),
                             rounds=200, batch=32, seed=0, record_every=50,
                             theta_sol=sol)
        assert tr.dropped == 0 and tr.invalid == 0
        assert tr.delivered == 2 * tr.events
        assert np.isfinite(tr.theta_hist).all()
        # CL-ADMM should move every agent off its solitary model and shrink
        # the neighbor disagreement term over the run
        d0 = np.linalg.norm(tr.theta_hist[0] - sol)
        assert d0 > 0
        tabs = topo.tables
        live = np.arange(topo.k_max)[None, :] < tabs.deg_count[:, None]

        def disagreement(theta):
            diff = theta[:, None, :] - theta[tabs.nbr_idx]
            return float((live[:, :, None] * diff ** 2).sum())

        assert disagreement(tr.theta_hist[-1]) \
            < 0.5 * disagreement(np.asarray(sol))

    def test_accounting_invariant_under_faults(self, problem):
        topo, data, sol = problem
        cond = NetworkConditions(drop_prob=0.2, stale_prob=0.3,
                                 straggler_frac=0.3, straggler_factor=0.1,
                                 churn_rate=0.02, partition_start=5,
                                 partition_end=25)
        tr = run_cl_scenario(topo, data, 0.1, 1.0, cond, rounds=60,
                             batch=32, seed=1, record_every=20,
                             theta_sol=sol)
        assert tr.dropped > 0
        assert tr.delivered + tr.dropped == 2 * (tr.events - tr.invalid)
        assert np.isfinite(tr.theta_hist).all()
        assert tr.active_hist[-1] <= 1.0

    def test_staleness_changes_trajectory(self, problem):
        topo, data, sol = problem
        kw = dict(rounds=80, batch=16, seed=3, record_every=20,
                  theta_sol=sol)
        clean = run_cl_scenario(topo, data, 0.1, 1.0, NetworkConditions(),
                                **kw)
        stale = run_cl_scenario(topo, data, 0.1, 1.0,
                                NetworkConditions(stale_prob=1.0), **kw)
        assert not np.array_equal(clean.theta_hist, stale.theta_hist)
        assert np.isfinite(stale.theta_hist).all()

    def test_drops_slow_consensus(self, problem):
        """Heavy loss must leave the run finite and measurably further from
        consensus than the clean run."""
        topo, data, sol = problem
        kw = dict(rounds=120, batch=16, seed=4, record_every=40,
                  theta_sol=sol)
        clean = run_cl_scenario(topo, data, 0.1, 1.0, NetworkConditions(),
                                **kw)
        lossy = run_cl_scenario(topo, data, 0.1, 1.0,
                                NetworkConditions(drop_prob=0.6), **kw)
        tabs = topo.tables
        live = np.arange(topo.k_max)[None, :] < tabs.deg_count[:, None]

        def disagreement(theta):
            diff = theta[:, None, :] - theta[tabs.nbr_idx]
            return float((live[:, :, None] * diff ** 2).sum())

        assert disagreement(lossy.theta_hist[-1]) \
            > disagreement(clean.theta_hist[-1])

    def test_recording_policy_clamped(self, problem):
        """rounds < record_every must not silently run zero rounds."""
        topo, data, sol = problem
        tr = run_cl_scenario(topo, data, 0.1, 1.0, NetworkConditions(),
                             rounds=5, batch=4, seed=0, record_every=100,
                             theta_sol=sol)
        assert tr.rounds == 5 and tr.events == 20
        assert tr.theta_hist.shape[0] == 1
        assert not np.array_equal(tr.theta_hist[-1], sol)


# ---------------------------------------------------------------------------
# The round's payload carry: partner rows for the next rounds in place of
# whole-state publish snapshots
# ---------------------------------------------------------------------------


def pub_prev_oracle(topo, data, sol, mu, rho, stream, record_every):
    """CL rounds with whole-array publish snapshots: each round publishes
    (post-primal theta and K, round-start L_own / L_nbr) whole, and the
    next round's stale deliveries read that snapshot at their partner
    cells.  Returns (theta per chunk, final state)."""
    tabs = topo.device_tables()
    n, k = tabs.nbr_w.shape
    mask = jnp.asarray(data.mask, jnp.float32)
    x = jnp.asarray(data.x, jnp.float32)
    m_counts = jnp.sum(mask, axis=1)
    sx = jnp.sum(x * mask[:, :, None], axis=1)
    D = jnp.asarray(tabs.deg_w, jnp.float32)
    st0 = init_sparse_admm(topo, sol)
    ev = engines._reshape_stream(stream, stream.i.shape[0] // record_every,
                                 record_every)

    def round_fn(carry, e):
        st, pv = carry
        upd = jnp.concatenate([e.i, e.j])
        got = jnp.concatenate([e.deliver_ji, e.deliver_ij])
        live = jnp.arange(k)[None, :] < tabs.deg_count[upd][:, None]
        new_theta, theta_js = batched_admm_primal(
            tabs.nbr_w[upd], live, st.Z_own[upd], st.Z_nbr[upd],
            st.L_own[upd], st.L_nbr[upd], D[upd], m_counts[upd], sx[upd],
            mu, rho)
        new_K = jnp.where(live[:, :, None], theta_js, st.K[upd])
        rowu = jnp.where(got, upd, n)
        theta = st.theta.at[rowu].set(new_theta, mode="drop")
        K = st.K.at[rowu].set(new_K, mode="drop")
        pub = (theta, K, st.L_own, st.L_nbr)
        own_s = jnp.concatenate([e.s, e.r])
        a = jnp.concatenate([e.j, e.i])
        s = jnp.concatenate([e.r, e.s])
        old = jnp.concatenate([e.stale_ji, e.stale_ij])[:, None]
        th_pay = jnp.where(old, pv[0][a], theta[a])
        k_pay = jnp.where(old, pv[1][a, s], K[a, s])
        lo_pay = jnp.where(old, pv[2][a, s], st.L_own[a, s])
        ln_pay = jnp.where(old, pv[3][a, s], st.L_nbr[a, s])
        theta_own, k_own = theta[upd], K[upd, own_s]
        l_own, l_nbr = st.L_own[upd, own_s], st.L_nbr[upd, own_s]
        z_own = 0.5 * ((l_own + ln_pay) / rho + theta_own + k_pay)
        z_nbr = 0.5 * ((lo_pay + l_nbr) / rho + th_pay + k_own)
        lo_new = l_own + rho * (theta_own - z_own)
        ln_new = l_nbr + rho * (k_own - z_nbr)
        st = SparseADMMState(
            theta, K, st.Z_own.at[rowu, own_s].set(z_own, mode="drop"),
            st.Z_nbr.at[rowu, own_s].set(z_nbr, mode="drop"),
            st.L_own.at[rowu, own_s].set(lo_new, mode="drop"),
            st.L_nbr.at[rowu, own_s].set(ln_new, mode="drop"))
        return (st, pub), None

    def chunk(carry, blk):
        carry, _ = jax.lax.scan(round_fn, carry, blk)
        return carry, carry[0].theta

    pub0 = (st0.theta, st0.K, st0.L_own, st0.L_nbr)
    (final, _), hist = jax.jit(
        lambda c, e: jax.lax.scan(chunk, c, e))((st0, pub0), ev)
    return np.asarray(hist), final


@pytest.fixture(scope="module")
def holey_problem():
    """60 agents on a ring with skip-2 chords (k_max = 4); agents 7, 22,
    37 and 52 have no neighbours, so their wake-ups are never-valid events
    whose partner cells are the tables' padding."""
    ring = [v for v in range(60) if v % 15 != 7]
    rows = [np.array([], np.int64)] * 60
    m = len(ring)
    for a, v in enumerate(ring):
        rows[v] = np.sort([ring[(a + d) % m] for d in (-2, -1, 1, 2)])
    topo = SparseTopology(
        tables_from_adjacency(rows, [np.ones(len(r)) for r in rows],
                              allow_isolated=True),
        (np.arange(60) >= 30).astype(np.int32))
    rng = np.random.default_rng(3)
    xs = [rng.standard_normal((int(rng.integers(1, 6)), 5))
          for _ in range(60)]
    data = pad_datasets(xs, [rng.standard_normal(len(x)) for x in xs])
    return topo, data, np.asarray(solitary_mean(data), np.float32)


class TestPayloadCarry:
    @pytest.mark.parametrize("cond,tel", [
        (NetworkConditions(drop_prob=0.1, stale_prob=0.3), False),
        (NetworkConditions(churn_rate=0.05, stale_prob=0.2), False),
        (NetworkConditions(stale_prob=1.0), False),
        (NetworkConditions(), False),
        (NetworkConditions(drop_prob=0.1, stale_prob=0.3), True),
    ], ids=["drop-stale", "churn-stale", "all-stale", "clean", "telemetry"])
    def test_bitwise_equal_to_whole_snapshot_rounds(self, holey_problem,
                                                    cond, tel):
        """The carried payload rows give bit for bit what whole-array
        publish snapshots give: 23 rounds in chunks of 5 (the t+1 / t+2
        lookahead crosses every chunk boundary), B = 9 with never-valid
        events among them."""
        topo, data, sol = holey_problem
        rounds, re_, B = 23, 5, 9
        total = record_chunks(rounds, re_)[1] * re_
        stream = precompute_event_stream(
            topo.device_tables(), jnp.asarray(topo.partition_halves()),
            cond, B, 11, total)
        tr = engines.run_cl_scenario(
            topo, data, 0.2, 0.7, cond, rounds=rounds, batch=B, seed=11,
            record_every=re_, theta_sol=sol, stream=stream,
            telemetry=TelemetryConfig(enabled=True) if tel else None)
        assert tr.invalid > 0
        if cond.stale_prob:
            assert np.asarray(stream.stale_ij).any()
        hist, final = pub_prev_oracle(topo, data, sol, 0.2, 0.7, stream, re_)
        assert np.array_equal(tr.theta_hist, hist)
        for name in SparseADMMState.__dataclass_fields__:
            assert np.array_equal(np.asarray(getattr(tr.final, name)),
                                  np.asarray(getattr(final, name))), name

    def test_round_loop_copies_no_whole_state(self, problem):
        """Compiled for the CPU, no computation of the CL scan other than
        its entry (the warm start) copies an (n, k_max, p) f32 array: the
        round carries partner rows, not whole-state snapshots held live
        across the scatters that overwrite the state."""
        topo = problem[0]
        tabs = topo.device_tables()
        n, k = tabs.nbr_w.shape
        p = 7
        re_, B = 4, 12
        stream = precompute_event_stream(
            tabs, jnp.asarray(topo.partition_halves()),
            NetworkConditions(drop_prob=0.1, stale_prob=0.05), B, 0, 2 * re_)
        ev = engines._reshape_stream(stream, 2, re_)
        f = lambda *shape: jnp.zeros(shape, jnp.float32)
        hlo = engines._cl_scenario_scan.lower(
            tabs.nbr_w, tabs.deg_count, f(n), f(n), f(n, p),
            (tabs.nbr_idx, f(n, p)), ev, mu=0.1, rho=1.0).compile().as_text()
        assert " while(" in hlo
        whole = f"f32[{n},{k},{p}]"
        comps = re.split(r"\n(?=\S)", hlo)       # one per computation
        assert sum(c.startswith("ENTRY") for c in comps) == 1
        copies = [line.strip() for c in comps if not c.startswith("ENTRY")
                  for line in c.splitlines()
                  if re.search(rf"= {re.escape(whole)}(\{{[^}}]*\}})? copy\(",
                               line)]
        assert not copies, copies
