"""Fused gossip-round megakernel (``kernels.round_fuse``, DESIGN.md §15):
parity of the fused-XLA and Pallas-interpret realizations against the
``ref.gossip_round_step`` oracle (incl. the acceptance maxerr <= 1e-6
bound), id-column winner resolution under duplicate targets, first-receipt
base-swap semantics, telescoped-update drift over chained prefetched
rounds, and engine-level agreement of the fused ``run_mp_scenario`` path
with the historic per-op program."""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref, round_fuse
from repro.kernels.dispatch import ReproBackend
from repro.simulate import (NetworkConditions, random_geometric_topology,
                            ring_topology, run_mp_scenario)


def make_state(n, k, p, seed=0):
    """Random round_step state over the flat id-column slot table."""
    rng = np.random.default_rng(seed)
    f32 = jnp.float32
    K = jnp.asarray(rng.standard_normal((n, k, p)), f32)
    return dict(
        theta=jnp.asarray(rng.standard_normal((n, p)), f32),
        Ke=round_fuse.encode_slots(K),
        got_ever=jnp.asarray(rng.uniform(size=n) < 0.5)), rng


def make_events(rng, n, k, p, m, collision_free=True, deliver_frac=0.7):
    """Prefetched event operands; collision-free targets by default
    (duplicate-winner semantics get their own controlled tests)."""
    if collision_free:
        codes = rng.choice(n * k, size=m, replace=False)
    else:
        codes = rng.integers(0, n * k, m)
    deliver = rng.uniform(size=m) < deliver_frac
    f32, i32 = jnp.float32, jnp.int32
    return dict(
        msg=jnp.asarray(rng.standard_normal((m, p)), f32),
        tgt_row=jnp.asarray(np.where(deliver, codes // k, n), i32),
        enc=jnp.asarray(np.where(deliver, codes, n * k), i32),
        k_old=jnp.asarray(rng.standard_normal((m, p)), f32))


def make_consts(rng, n, k, p):
    f32 = jnp.float32
    return dict(theta_base=jnp.asarray(rng.standard_normal((n, p)), f32),
                a_w=jnp.asarray(rng.uniform(0.1, 1.0, n * k), f32))


def run_all(state, events, consts, block_b=128):
    args = (*state.values(), *events.values(), *consts.values())
    want = ref.gossip_round_step(*args)
    got_x = round_fuse.round_step_xla(*args)
    got_p = round_fuse.round_step_pallas(*args, block_b=block_b,
                                         interpret=True)
    return want, got_x, got_p


def assert_close(got, want, atol=1e-6):
    assert np.abs(np.asarray(got[0]) - np.asarray(want[0])).max() <= atol
    assert np.abs(np.asarray(got[1]) - np.asarray(want[1])).max() <= atol
    assert np.array_equal(np.asarray(got[2]), np.asarray(want[2]))  # got_ever
    assert np.array_equal(np.asarray(got[3]), np.asarray(want[3]))  # keep


class TestRoundStepParity:
    @pytest.mark.parametrize("seed", [0, 1, 2])
    def test_acceptance_maxerr(self, seed):
        """Acceptance: fused XLA and Pallas (interpret) within 1e-6 of the
        oracle on collision-free batches; K is exact (same landed rows)."""
        state, rng = make_state(41, 6, 9, seed=seed)
        events = make_events(rng, 41, 6, 9, 48)
        consts = make_consts(rng, 41, 6, 9)
        want, got_x, got_p = run_all(state, events, consts)
        for got in (got_x, got_p):
            assert np.abs(np.asarray(got[1]) - np.asarray(want[1])).max() \
                == 0.0                                       # Ke exact
            assert_close(got, want)

    def test_event_padding(self):
        """2B not a multiple of block_b (nor even): pads must be no-ops."""
        state, rng = make_state(23, 4, 5, seed=3)
        events = make_events(rng, 23, 4, 5, 13)
        consts = make_consts(rng, 23, 4, 5)
        want, got_x, got_p = run_all(state, events, consts, block_b=4)
        for got in (got_x, got_p):
            assert_close(got, want)

    def test_nothing_delivered_is_identity(self):
        """All targets at the sentinels: every output comes back
        bit-identical and no event keeps."""
        state, rng = make_state(17, 3, 4, seed=4)
        events = make_events(rng, 17, 3, 4, 10, deliver_frac=0.0)
        consts = make_consts(rng, 17, 3, 4)
        for got in run_all(state, events, consts)[1:]:
            for g, w in zip(got[:3], state.values()):
                assert np.abs(np.asarray(g).astype(np.float32)
                              - np.asarray(w).astype(np.float32)).max() \
                    == 0.0
            assert not np.asarray(got[3]).any()

    def test_first_receipt_swaps_in_base(self):
        """A row receiving for the first time telescopes from theta_base,
        not its warm-start theta; an already-seen row accumulates."""
        n, k, p = 9, 2, 3
        state, rng = make_state(n, k, p, seed=5)
        state["got_ever"] = jnp.asarray([False] * 5 + [True] * 4)
        consts = make_consts(rng, n, k, p)
        f32, i32 = jnp.float32, jnp.int32
        msg = jnp.asarray(rng.standard_normal((2, p)), f32)
        k_old = jnp.asarray(rng.standard_normal((2, p)), f32)
        events = dict(msg=msg,
                      tgt_row=jnp.asarray([2, 7], i32),     # fresh, seen
                      enc=jnp.asarray([2 * k, 7 * k + 1], i32),
                      k_old=k_old)
        for got in run_all(state, events, consts)[1:]:
            theta, _, got_ever, keep = got
            aw = np.asarray(consts["a_w"])
            d0 = aw[2 * k] * (np.asarray(msg[0]) - np.asarray(k_old[0]))
            d1 = aw[7 * k + 1] * (np.asarray(msg[1]) - np.asarray(k_old[1]))
            np.testing.assert_allclose(
                np.asarray(theta[2]),
                np.asarray(consts["theta_base"][2]) + d0, atol=1e-6)
            np.testing.assert_allclose(
                np.asarray(theta[7]),
                np.asarray(state["theta"][7]) + d1, atol=1e-6)
            assert np.asarray(got_ever)[[2, 7]].all()
            assert np.asarray(keep).all()

    def test_duplicate_targets_identical_payload(self):
        """Duplicate deliveries of the *same* payload to one slot:
        resolution order cannot matter, so every realization must agree
        with the oracle exactly (modulo which id survives)."""
        n, k, p = 13, 4, 5
        state, rng = make_state(n, k, p, seed=6)
        consts = make_consts(rng, n, k, p)
        f32, i32 = jnp.float32, jnp.int32
        one = jnp.asarray(rng.standard_normal((1, p)), f32)
        kold = jnp.asarray(rng.standard_normal((1, p)), f32)
        events = dict(msg=jnp.concatenate([one, one]),
                      tgt_row=jnp.asarray([5, 5], i32),
                      enc=jnp.asarray([5 * k + 3, 5 * k + 3], i32),
                      k_old=jnp.concatenate([kold, kold]))
        want, got_x, got_p = run_all(state, events, consts, block_b=1)
        for got in (got_x, got_p):
            for g, w in zip(got[:2], want[:2]):
                assert np.abs(np.asarray(g)[:, :p]
                              - np.asarray(w)[:, :p]).max() <= 1e-6
            assert np.asarray(got[3]).sum() == 1      # exactly one winner

    def test_duplicate_targets_conflicting_payload(self):
        """Conflicting duplicate deliveries to one slot: each realization
        must be *self-consistent* — the surviving id names the winner, the
        slot holds the winner's message, and theta telescopes the winner's
        delta — the documented divergence point between XLA scatter
        semantics and Pallas event-order resolution."""
        n, k, p = 13, 4, 5
        state, rng = make_state(n, k, p, seed=7)
        state["got_ever"] = jnp.ones((n,), bool)     # isolate the delta path
        consts = make_consts(rng, n, k, p)
        f32, i32 = jnp.float32, jnp.int32
        msg = jnp.asarray(rng.standard_normal((2, p)), f32)
        kold = np.asarray(state["Ke"])[5 * k + 3, :p][None]
        events = dict(msg=msg, tgt_row=jnp.asarray([5, 5], i32),
                      enc=jnp.asarray([5 * k + 3, 5 * k + 3], i32),
                      k_old=jnp.asarray(np.concatenate([kold, kold]), f32))
        want, got_x, got_p = run_all(state, events, consts, block_b=1)
        aw = float(np.asarray(consts["a_w"])[5 * k + 3])
        for got in (want, got_x, got_p):
            theta, Ke, _, keep = (np.asarray(a) for a in got)
            (win,) = np.nonzero(keep)
            assert Ke[5 * k + 3, p] == win[0]         # id names the winner
            np.testing.assert_array_equal(Ke[5 * k + 3, :p],
                                          np.asarray(msg[win[0]]))
            np.testing.assert_allclose(
                theta[5], np.asarray(state["theta"][5])
                + aw * (np.asarray(msg[win[0]]) - kold[0]), atol=1e-6)
        # the xla two-half scatter resolves like the oracle's keep-last
        assert np.abs(np.asarray(got_x[1]) - np.asarray(want[1])).max() == 0.0

    def test_winner_uniqueness_under_collisions(self):
        """Random colliding batch: exactly one keep per landed slot, none
        at the sentinel."""
        n, k, p = 11, 3, 4
        state, rng = make_state(n, k, p, seed=8)
        events = make_events(rng, n, k, p, 40, collision_free=False)
        consts = make_consts(rng, n, k, p)
        for got in run_all(state, events, consts)[1:]:
            keep = np.asarray(got[3])
            enc = np.asarray(events["enc"])
            for e in np.unique(enc[enc < n * k]):
                assert keep[enc == e].sum() == 1
            assert not keep[enc == n * k].any()

    def test_chained_rounds_stay_coherent(self):
        """30 rounds chained through the carry (the engine's layout): xla
        and the oracle stay within 1e-6 and the slot table stays exact,
        i.e. the telescoped theta does not drift."""
        n, k, p = 37, 5, 8
        state, rng = make_state(n, k, p, seed=9)
        consts = make_consts(rng, n, k, p)
        sx = so = tuple(state.values())
        for r in range(30):
            events = make_events(rng, n, k, p, 24)
            sx = round_fuse.round_step_xla(*sx, *events.values(),
                                           *consts.values())[:3]
            so = ref.gossip_round_step(*so, *events.values(),
                                       *consts.values())[:3]
        assert np.abs(np.asarray(sx[1]) - np.asarray(so[1])).max() == 0.0
        assert np.abs(np.asarray(sx[0]) - np.asarray(so[0])).max() <= 1e-6

    def test_round_prefetch_contract(self):
        """round_prefetch gathers stale senders from theta_prev, encodes
        undelivered targets at the sentinels, and reads pre-scatter slot
        values."""
        n, k, p = 7, 2, 3
        state, rng = make_state(n, k, p, seed=10)
        f32, i32 = jnp.float32, jnp.int32
        theta_prev = jnp.asarray(rng.standard_normal((n, p)), f32)
        msg, tgt_row, enc, k_old = round_fuse.round_prefetch(
            state["theta"], theta_prev, state["Ke"],
            jnp.asarray([1, 2], i32), jnp.asarray([3, 4], i32),   # i, j
            jnp.asarray([0, 1], i32), jnp.asarray([1, 0], i32),   # s, r
            jnp.asarray([True, False]), jnp.asarray([False, True]),
            jnp.asarray([False, True]), jnp.asarray([True, False]))
        # senders: [i0, i1, j0, j1]; stale i1 and j0 read theta_prev
        np.testing.assert_array_equal(np.asarray(msg), np.asarray(
            jnp.stack([state["theta"][1], theta_prev[2],
                       theta_prev[3], state["theta"][4]])))
        # delivered: i0 -> row 3 slot r=1, j1 -> row 2 slot s=1
        np.testing.assert_array_equal(np.asarray(tgt_row), [3, n, n, 2])
        np.testing.assert_array_equal(np.asarray(enc),
                                      [3 * k + 1, n * k, n * k, 2 * k + 1])
        np.testing.assert_array_equal(
            np.asarray(k_old[0]), np.asarray(state["Ke"])[3 * k + 1, :p])

    def test_slot_codecs_roundtrip(self):
        rng = np.random.default_rng(11)
        K = jnp.asarray(rng.standard_normal((6, 3, 4)), jnp.float32)
        Ke = round_fuse.encode_slots(K)
        assert Ke.shape == (18, 5)
        assert np.all(np.asarray(Ke[:, 4]) == -1.0)
        np.testing.assert_array_equal(
            np.asarray(round_fuse.decode_slots(Ke, 3)), np.asarray(K))


class TestClEdgeStepPallas:
    def test_parity_with_padding(self):
        """Pallas cl_edge_step vs the reference callable, E not a multiple
        of block_b (collision-free targets; engine-level duplicate handling
        rides the existing CL parity suites)."""
        n, k, p, E = 19, 4, 6, 11
        rng = np.random.default_rng(9)
        f32 = jnp.float32
        a3 = lambda: jnp.asarray(rng.standard_normal((n, k, p)), f32)
        a2 = lambda: jnp.asarray(rng.standard_normal((n, p)), f32)
        rows = lambda: jnp.asarray(rng.standard_normal((E, p)), f32)
        codes = rng.choice(n * k, size=E, replace=False)
        args = (a2(), a3(), a3(), a3(), a3(), a3(),
                rows(), rows(), rows(), rows(), rows(), rows(),
                jnp.asarray(codes // k, jnp.int32),
                jnp.asarray(codes % k, jnp.int32),
                jnp.asarray(rng.integers(0, n, E), jnp.int32),
                jnp.asarray(rng.integers(0, k, E), jnp.int32),
                jnp.asarray(rng.uniform(size=E) < 0.4),
                jnp.asarray(rng.uniform(size=E) < 0.7))
        want = round_fuse.cl_edge_step(*args, rho=1.3)
        got = round_fuse.cl_edge_step_pallas(*args, rho=1.3, block_b=4,
                                             interpret=True)
        for g, w in zip(got, want):
            assert np.abs(np.asarray(g) - np.asarray(w)).max() <= 1e-6


class TestEngineFusedPath:
    def test_fused_xla_matches_default_engine(self):
        """run_mp_scenario(backend=...) executes the same scenario through
        the fused round_step: identical counters, trajectory within fp
        rounding of the historic per-op program."""
        topo = random_geometric_topology(200, k=5, seed=0)
        rng = np.random.default_rng(0)
        sol = rng.standard_normal((200, 6)).astype(np.float32)
        c = rng.uniform(0.05, 1.0, 200).astype(np.float32)
        cond = NetworkConditions(drop_prob=0.1, stale_prob=0.3,
                                 churn_rate=0.01, straggler_frac=0.3,
                                 partition_start=10, partition_end=30)
        tr = run_mp_scenario(topo, sol, c, 0.9, cond, rounds=50, batch=32,
                             seed=3, record_every=10)
        fu = run_mp_scenario(topo, sol, c, 0.9, cond, rounds=50, batch=32,
                             seed=3, record_every=10,
                             backend=ReproBackend.using(round_step="xla"))
        np.testing.assert_allclose(fu.theta_hist, tr.theta_hist, atol=1e-5)
        np.testing.assert_allclose(fu.active_hist, tr.active_hist)
        assert (fu.delivered, fu.dropped, fu.invalid, fu.rounds, fu.events) \
            == (tr.delivered, tr.dropped, tr.invalid, tr.rounds, tr.events)

    def test_fused_pallas_interpret_matches_default_engine(self):
        """The Pallas megakernel (interpret mode) driving the engine on a
        small problem: same trajectory within fp rounding."""
        topo = ring_topology(40)
        rng = np.random.default_rng(1)
        sol = rng.standard_normal((40, 4)).astype(np.float32)
        c = rng.uniform(0.05, 1.0, 40).astype(np.float32)
        cond = NetworkConditions(drop_prob=0.1, stale_prob=0.2)
        tr = run_mp_scenario(topo, sol, c, 0.9, cond, rounds=10, batch=8,
                             seed=2, record_every=5)
        fu = run_mp_scenario(
            topo, sol, c, 0.9, cond, rounds=10, batch=8, seed=2,
            record_every=5,
            backend=ReproBackend.using(round_step="pallas", interpret=True))
        np.testing.assert_allclose(fu.theta_hist, tr.theta_hist, atol=1e-5)
        assert (fu.delivered, fu.dropped, fu.invalid) \
            == (tr.delivered, tr.dropped, tr.invalid)

    def test_fused_telemetry_matches_default_engine(self):
        """Telemetry accumulators ride the fused carry unchanged: frames
        agree with the default path (objective to fp rounding, counters
        exactly)."""
        from repro.telemetry import TelemetryConfig
        topo = random_geometric_topology(120, k=4, seed=2)
        rng = np.random.default_rng(4)
        sol = rng.standard_normal((120, 5)).astype(np.float32)
        c = rng.uniform(0.05, 1.0, 120).astype(np.float32)
        cond = NetworkConditions(drop_prob=0.15, stale_prob=0.2,
                                 churn_rate=0.02)
        kw = dict(rounds=40, batch=24, seed=5, record_every=10,
                  telemetry=TelemetryConfig(enabled=True))
        tr = run_mp_scenario(topo, sol, c, 0.9, cond, **kw)
        fu = run_mp_scenario(topo, sol, c, 0.9, cond, **kw,
                             backend=ReproBackend.using(round_step="xla"))
        np.testing.assert_allclose(fu.telemetry.objective,
                                   tr.telemetry.objective, rtol=1e-5)
        for f in ("staleness", "updates", "delivered", "drop_link",
                  "drop_churn", "drop_partition", "invalid"):
            np.testing.assert_array_equal(getattr(fu.telemetry, f),
                                          getattr(tr.telemetry, f), err_msg=f)
