"""Per-kernel validation: Pallas (interpret=True) vs pure-jnp oracle,
sweeping shapes/dtypes, plus hypothesis property tests (deliverable c)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip("hypothesis")  # optional dev dep (requirements-dev.txt)
from hypothesis import given, settings, strategies as st

from repro.kernels import ops, ref


def tol(dtype):
    return dict(atol=2e-2, rtol=2e-2) if dtype == jnp.bfloat16 else \
        dict(atol=1e-5, rtol=1e-5)


# ---------------------------------------------------------------------------
# graph_mix
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("n", [4, 16, 32, 100])
@pytest.mark.parametrize("D", [64, 512, 1000])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_graph_mix_matches_ref(n, D, dtype):
    key = jax.random.PRNGKey(n * 1000 + D)
    k1, k2, k3, k4 = jax.random.split(key, 4)
    theta = jax.random.normal(k1, (n, D), dtype)
    sol = jax.random.normal(k2, (n, D), dtype)
    A = jax.random.uniform(k3, (n, n), jnp.float32) / n
    b = jax.random.uniform(k4, (n,), jnp.float32)
    got = ops.graph_mix(theta, sol, A, b, interpret=True)
    want = ref.graph_mix(theta, sol, A, b)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **tol(dtype))


def test_graph_mix_is_mp_step():
    """The kernel computes exactly the paper's Eq. (5) iterate."""
    from repro.core import gaussian_kernel_graph, synchronous
    rng = np.random.default_rng(0)
    n, p = 12, 40
    g = gaussian_kernel_graph(rng.standard_normal((n, 2)), sigma=1.0)
    theta_sol = rng.standard_normal((n, p)).astype(np.float32)
    c = rng.uniform(0.1, 1.0, n).astype(np.float32)
    alpha = 0.9
    abar = 1 - alpha
    denom = alpha + abar * c
    A = (alpha / denom)[:, None] * np.asarray(g.P, np.float32)
    b = abar * c / denom
    one_step = ops.graph_mix(jnp.asarray(theta_sol), jnp.asarray(theta_sol),
                             jnp.asarray(A), jnp.asarray(b), interpret=True)
    want = synchronous(g, theta_sol, c, alpha, steps=1)
    np.testing.assert_allclose(np.asarray(one_step), np.asarray(want),
                               atol=1e-4, rtol=1e-4)


@settings(max_examples=20, deadline=None)
@given(n=st.integers(2, 24), D=st.integers(1, 300))
def test_graph_mix_property_random_shapes(n, D):
    key = jax.random.PRNGKey(n * 7 + D)
    k1, k2, k3, k4 = jax.random.split(key, 4)
    theta = jax.random.normal(k1, (n, D))
    sol = jax.random.normal(k2, (n, D))
    A = jax.random.uniform(k3, (n, n)) / n
    b = jax.random.uniform(k4, (n,))
    got = ops.graph_mix(theta, sol, A, b, interpret=True)
    want = ref.graph_mix(theta, sol, A, b)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-4,
                               rtol=1e-4)


# ---------------------------------------------------------------------------
# flash_attention
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("S,block", [(128, 64), (256, 64), (512, 128)])
@pytest.mark.parametrize("window", [None, 64])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_matches_ref(S, block, window, dtype):
    B, H, hd = 2, 2, 64
    key = jax.random.PRNGKey(S + (window or 0))
    k1, k2, k3 = jax.random.split(key, 3)
    q = jax.random.normal(k1, (B, S, H, hd), dtype)
    k = jax.random.normal(k2, (B, S, H, hd), dtype)
    v = jax.random.normal(k3, (B, S, H, hd), dtype)
    got = ops.flash_attention(q, k, v, window=window, block_q=block,
                              block_k=block, interpret=True)
    want = ref.flash_attention(q, k, v, window=window)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **tol(dtype))


def test_flash_attention_gqa_expansion():
    B, S, H, K, hd = 1, 128, 8, 2, 32
    key = jax.random.PRNGKey(0)
    k1, k2, k3 = jax.random.split(key, 3)
    q = jax.random.normal(k1, (B, S, H, hd))
    k = jax.random.normal(k2, (B, S, K, hd))
    v = jax.random.normal(k3, (B, S, K, hd))
    got = ops.flash_attention(q, k, v, block_q=64, block_k=64,
                              interpret=True)
    kf = jnp.repeat(k, H // K, axis=2)
    vf = jnp.repeat(v, H // K, axis=2)
    want = ref.flash_attention(q, kf, vf)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-4,
                               rtol=1e-4)


def test_flash_attention_matches_model_chunked_path():
    """Kernel vs the model engine's chunked_attention (same math)."""
    from repro.models.attention import chunked_attention
    B, S, H, hd = 1, 256, 4, 32
    key = jax.random.PRNGKey(7)
    k1, k2, k3 = jax.random.split(key, 3)
    q = jax.random.normal(k1, (B, S, H, hd))
    k = jax.random.normal(k2, (B, S, H, hd))
    v = jax.random.normal(k3, (B, S, H, hd))
    got = ops.flash_attention(q, k, v, block_q=64, block_k=64,
                              interpret=True)
    want = chunked_attention(q, k, v, chunk=64)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-4,
                               rtol=1e-4)


@settings(max_examples=10, deadline=None)
@given(sblk=st.sampled_from([(64, 32), (128, 64), (192, 64)]),
       window=st.sampled_from([None, 32, 100]),
       hd=st.sampled_from([16, 64]))
def test_flash_attention_property(sblk, window, hd):
    S, block = sblk
    B, H = 1, 2
    key = jax.random.PRNGKey(S + hd)
    k1, k2, k3 = jax.random.split(key, 3)
    q = jax.random.normal(k1, (B, S, H, hd))
    k = jax.random.normal(k2, (B, S, H, hd))
    v = jax.random.normal(k3, (B, S, H, hd))
    got = ops.flash_attention(q, k, v, window=window, block_q=block,
                              block_k=block, interpret=True)
    want = ref.flash_attention(q, k, v, window=window)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-4,
                               rtol=1e-4)


# ---------------------------------------------------------------------------
# admm_edge_update
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("E,p", [(1, 16), (8, 512), (13, 100), (64, 1000)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_admm_update_matches_ref(E, p, dtype):
    key = jax.random.PRNGKey(E * p)
    args = [jax.random.normal(k, (E, p), dtype)
            for k in jax.random.split(key, 8)]
    rho = 1.5
    got = ops.admm_edge_update(*args, rho=rho, interpret=True)
    want = ref.admm_edge_update(*args, rho=rho)
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g, np.float32),
                                   np.asarray(w, np.float32), **tol(dtype))


def test_admm_update_matches_core_algorithm():
    """Kernel == the reference decentralized ADMM edge update (step 2-3)."""
    from repro.core import gaussian_kernel_graph
    from repro.core.collaborative import init_state, _all_zl_update, ADMMState
    rng = np.random.default_rng(3)
    n, p = 6, 4
    g = gaussian_kernel_graph(rng.standard_normal((n, 2)), sigma=1.0)
    theta = rng.standard_normal((n, p)).astype(np.float32)
    st0 = init_state(g, theta)
    # randomize duals/copies to make the check non-trivial
    key = jax.random.PRNGKey(0)
    ks = jax.random.split(key, 4)
    st0 = ADMMState(st0.T + 0.1 * jax.random.normal(ks[0], st0.T.shape),
                    st0.Z_own, st0.Z_nbr,
                    0.1 * jax.random.normal(ks[1], st0.L_own.shape),
                    0.1 * jax.random.normal(ks[2], st0.L_nbr.shape))
    rho = 1.3
    mask = jnp.asarray(g.W > 0)
    st1 = _all_zl_update(st0, mask, rho)
    edges = g.edges()
    ii = np.array([e[0] for e in edges])
    jj = np.array([e[1] for e in edges])
    T = np.asarray(st0.T)
    z_i, z_j, loi, lnj, loj, lni = ops.admm_edge_update(
        jnp.asarray(T[ii, ii]), jnp.asarray(T[jj, ii]),
        jnp.asarray(T[jj, jj]), jnp.asarray(T[ii, jj]),
        jnp.asarray(np.asarray(st0.L_own)[ii, jj]),
        jnp.asarray(np.asarray(st0.L_nbr)[ii, jj]),
        jnp.asarray(np.asarray(st0.L_own)[jj, ii]),
        jnp.asarray(np.asarray(st0.L_nbr)[jj, ii]),
        rho=rho, interpret=True)
    np.testing.assert_allclose(np.asarray(z_i),
                               np.asarray(st1.Z_own)[ii, jj], atol=1e-5)
    np.testing.assert_allclose(np.asarray(z_j),
                               np.asarray(st1.Z_own)[jj, ii], atol=1e-5)
    np.testing.assert_allclose(np.asarray(loi),
                               np.asarray(st1.L_own)[ii, jj], atol=1e-5)
    np.testing.assert_allclose(np.asarray(loj),
                               np.asarray(st1.L_own)[jj, ii], atol=1e-5)
    np.testing.assert_allclose(np.asarray(lnj),
                               np.asarray(st1.L_nbr)[ii, jj], atol=1e-5)
    np.testing.assert_allclose(np.asarray(lni),
                               np.asarray(st1.L_nbr)[jj, ii], atol=1e-5)
