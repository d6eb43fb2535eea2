"""Coupling layer: gossip schedule == dense operator == paper's Eq. (5);
matchings cover the graph; consensus/cl operators behave as specified."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (ring_graph, random_geometric_graph,
                        closed_form, synchronous)
from repro.coupling import (CouplingConfig, make_state, make_coupling,
                            dense_mix_tree, consensus_mean_tree,
                            laplacian_pull_tree)


def tiny_tree(A, key=0):
    rng = np.random.default_rng(key)
    return {"w": jnp.asarray(rng.standard_normal((A, 8, 16)), jnp.float32),
            "b": jnp.asarray(rng.standard_normal((A, 4)), jnp.float32)}


class TestMatchings:
    @pytest.mark.parametrize("n,k", [(8, 2), (16, 3), (32, 3)])
    def test_edge_coloring_covers_and_disjoint(self, n, k):
        g = random_geometric_graph(n, k=k, seed=1)
        matchings = g.edge_coloring()
        seen = set()
        for m in matchings:
            nodes = [x for e in m for x in e]
            assert len(nodes) == len(set(nodes)), "matching not disjoint"
            seen.update(frozenset(e) for e in m)
        assert seen == {frozenset(e) for e in g.edges()}


class TestMPCoupling:
    def test_dense_mix_is_eq5_iterate(self):
        """dense_mix_tree == one step of the paper's synchronous iteration."""
        A = 12
        g = random_geometric_graph(A, k=3, seed=0)
        conf = np.linspace(0.1, 1.0, A)
        alpha = 0.95
        state = make_state(g, conf, alpha)
        rng = np.random.default_rng(1)
        theta = rng.standard_normal((A, 24)).astype(np.float32)
        sol = rng.standard_normal((A, 24)).astype(np.float32)
        cfg = CouplingConfig(mode="mp", alpha=alpha)
        got = dense_mix_tree({"t": jnp.asarray(theta)}, {"t": jnp.asarray(sol)},
                             state, cfg)["t"]
        # Eq. (5) starting from theta with anchor sol
        want = np.asarray(synchronous(g, sol, conf, alpha, steps=1,
                                      theta0=theta))
        np.testing.assert_allclose(np.asarray(got), want, atol=1e-5)

    def test_repeated_mixing_converges_to_closed_form(self):
        """Iterating the coupling operator -> Prop. 1 optimum (C1 at scale)."""
        A = 16
        g = random_geometric_graph(A, k=3, seed=2)
        conf = np.linspace(0.2, 1.0, A)
        alpha = 0.9
        state = make_state(g, conf, alpha)
        cfg = CouplingConfig(mode="mp", alpha=alpha)
        rng = np.random.default_rng(3)
        sol = {"t": jnp.asarray(rng.standard_normal((A, 6)), jnp.float32)}
        theta = sol
        for _ in range(400):
            theta = dense_mix_tree(theta, sol, state, cfg)
        star = np.asarray(closed_form(g, np.asarray(sol["t"]), conf, alpha))
        np.testing.assert_allclose(np.asarray(theta["t"]), star, atol=1e-4)

    def test_kernel_path_matches_einsum_path(self, monkeypatch):
        # use_kernel requests the Pallas impl; off-TPU it runs only with
        # the explicit interpret opt-in
        monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "1")
        A = 8
        g = ring_graph(A)
        state = make_state(g, np.ones(A) * 0.5, 0.9)
        tree = tiny_tree(A)
        sol = tiny_tree(A, key=9)
        a = dense_mix_tree(tree, sol, state, CouplingConfig(mode="mp"))
        b = dense_mix_tree(tree, sol, state,
                           CouplingConfig(mode="mp", use_kernel=True))
        for k in tree:
            np.testing.assert_allclose(np.asarray(a[k]), np.asarray(b[k]),
                                       atol=1e-5)


class TestOtherModes:
    def test_consensus_is_uniform_mean(self):
        A = 6
        tree = tiny_tree(A)
        out = consensus_mean_tree(tree, CouplingConfig(mode="consensus"))
        for k in tree:
            want = np.mean(np.asarray(tree[k]), axis=0, keepdims=True)
            np.testing.assert_allclose(np.asarray(out[k]),
                                       np.broadcast_to(want, tree[k].shape),
                                       atol=1e-6)

    def test_laplacian_pull_is_qcl_smoothness_gradient(self):
        A = 10
        g = random_geometric_graph(A, k=3, seed=4)
        state = make_state(g, np.ones(A), 0.9)
        tree = tiny_tree(A)
        lr = 0.01
        out = laplacian_pull_tree(tree, state,
                                  CouplingConfig(mode="cl", mu=lr), lr=lr)
        W = jnp.asarray(g.W, jnp.float32)

        def smooth(t):
            diff = t[:, None] - t[None, :]
            return 0.5 * jnp.sum(W * jnp.sum(diff.reshape(A, A, -1) ** 2, -1))

        for k in tree:
            gexp = jax.grad(smooth)(tree[k])
            want = tree[k] - lr * gexp
            np.testing.assert_allclose(np.asarray(out[k]), np.asarray(want),
                                       atol=1e-5, rtol=1e-4)

    def test_every_k_gating(self):
        A = 4
        g = ring_graph(A)
        state = make_state(g, np.ones(A), 0.9)
        cfg = CouplingConfig(mode="mp", every=4)
        couple = make_coupling(cfg, state, ("data",))
        tree = tiny_tree(A)
        sol = tiny_tree(A, key=5)
        same = couple(tree, sol, jnp.asarray(1))
        mixed = couple(tree, sol, jnp.asarray(4))
        np.testing.assert_allclose(np.asarray(same["w"]),
                                   np.asarray(tree["w"]))
        assert not np.allclose(np.asarray(mixed["w"]), np.asarray(tree["w"]))
