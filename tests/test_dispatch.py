"""Backend dispatch registry: parity of every registered implementation
against ``reference`` (1e-5 on randomized inputs), selection rules, and the
no-direct-kernel-imports architecture invariant."""

import pathlib
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import dispatch, ref
from repro.kernels.dispatch import BackendUnavailable, ReproBackend, resolve


def _as_tuple(x):
    return x if isinstance(x, (tuple, list)) else (x,)


def _make_args(op, seed=0):
    """Randomized canonical-signature inputs (args, kwargs) for ``op``."""
    rng = np.random.default_rng(seed)
    f32 = jnp.float32
    if op == "mix":
        n, D = 12, 200
        return (jnp.asarray(rng.standard_normal((n, D)), f32),
                jnp.asarray(rng.standard_normal((n, D)), f32),
                jnp.asarray(rng.uniform(0, 1, (n, n)) / n, f32),
                jnp.asarray(rng.uniform(0, 1, n), f32)), {}
    if op == "sparse_mix":
        n, k, p = 50, 6, 40
        w = rng.uniform(0, 1, (n, k)).astype(np.float32)
        w[:, -1] = 0.0
        return (jnp.asarray(rng.standard_normal((n, p)), f32),
                jnp.asarray(rng.integers(0, n, (n, k)), jnp.int32),
                jnp.asarray(w),
                jnp.asarray(rng.uniform(0, 1, n), f32),
                jnp.asarray(rng.standard_normal((n, p)), f32)), {}
    if op == "admm_primal":
        k, p = 7, 20
        return (jnp.asarray(rng.uniform(0.1, 1, k), f32),
                jnp.asarray(rng.uniform(size=k) < 0.7),
                jnp.asarray(rng.standard_normal((k, p)), f32),
                jnp.asarray(rng.standard_normal((k, p)), f32),
                jnp.asarray(rng.standard_normal((k, p)), f32),
                jnp.asarray(rng.standard_normal((k, p)), f32),
                jnp.float32(2.5), jnp.float32(30.0),
                jnp.asarray(rng.standard_normal(p), f32),
                0.05, 1.3), {}
    if op == "admm_edge":
        E, p = 9, 33
        return tuple(jnp.asarray(rng.standard_normal((E, p)), f32)
                     for _ in range(8)), {"rho": 1.5}
    if op == "edge_reweight":
        B, k = 40, 7
        live = rng.uniform(size=(B, k)) < 0.8
        live[0] = False                       # an all-dead row stays zero
        w = rng.uniform(0, 1, (B, k)) * live
        w /= np.maximum(w.sum(axis=1, keepdims=True), 1e-9)
        return (jnp.asarray(rng.uniform(0, 4, (B, k)), f32),
                jnp.asarray(w, f32), jnp.asarray(live)), \
            {"eta": 0.3, "lam": 0.7}
    if op == "neighbor_aggregate":
        k, p = 9, 25
        return (jnp.asarray(rng.uniform(0, 1, k), f32),
                jnp.asarray(rng.standard_normal((k, p)), f32)), {}
    if op == "attention":
        B, S, H, hd = 1, 128, 2, 32
        ks = jax.random.split(jax.random.PRNGKey(seed), 3)
        return (jax.random.normal(ks[0], (B, S, H, hd)),
                jax.random.normal(ks[1], (B, S, H, hd)),
                jax.random.normal(ks[2], (B, S, H, hd))), {"window": 48}
    if op == "round_step":
        n, k, p, m = 31, 5, 12, 40
        from repro.kernels import round_fuse
        K = jnp.asarray(rng.standard_normal((n, k, p)), f32)
        # collision-free scatter targets: duplicate (row, slot) targets with
        # conflicting payloads are resolution-order-dependent (a valid
        # realization either way) — duplicate semantics get their own
        # controlled tests in tests/test_round_fuse.py
        codes = rng.choice(n * k, size=m, replace=False)
        deliver = rng.uniform(size=m) < 0.7
        return (jnp.asarray(rng.standard_normal((n, p)), f32),
                round_fuse.encode_slots(K),
                jnp.asarray(rng.uniform(size=n) < 0.5),   # got_ever
                jnp.asarray(rng.standard_normal((m, p)), f32),     # msg
                jnp.asarray(np.where(deliver, codes // k, n), jnp.int32),
                jnp.asarray(np.where(deliver, codes, n * k), jnp.int32),
                jnp.asarray(rng.standard_normal((m, p)), f32),     # k_old
                jnp.asarray(rng.standard_normal((n, p)), f32),     # base
                jnp.asarray(rng.uniform(0.1, 1, n * k), f32)), {}  # a_w
    if op == "cl_edge_step":
        n, k, p, E = 23, 4, 10, 18
        arr3 = lambda: jnp.asarray(rng.standard_normal((n, k, p)), f32)
        arr2 = lambda: jnp.asarray(rng.standard_normal((n, p)), f32)
        codes = rng.choice(n * k, size=E, replace=False)  # collision-free
        return (arr2(), arr3(), arr3(), arr3(), arr3(), arr3(),
                arr2(), arr3(), arr3(), arr3(),
                jnp.asarray(codes // k, jnp.int32),
                jnp.asarray(codes % k, jnp.int32),
                jnp.asarray(rng.integers(0, n, E), jnp.int32),
                jnp.asarray(rng.integers(0, k, E), jnp.int32),
                jnp.asarray(rng.uniform(size=E) < 0.4),
                jnp.asarray(rng.uniform(size=E) < 0.7)), {"rho": 1.1}
    if op == "admm_primal_inexact":
        from repro.core.losses import guarded_loss
        from repro.optim.adamw import AdamWConfig
        k, p, m = 5, 6, 11
        mask = (rng.uniform(size=m) < 0.7).astype(np.float32)
        return (jnp.asarray(rng.uniform(0.1, 1, k), f32),
                jnp.asarray(rng.uniform(size=k) < 0.7),
                jnp.asarray(rng.standard_normal((k, p)), f32),
                jnp.asarray(rng.standard_normal((k, p)), f32),
                jnp.asarray(rng.standard_normal((k, p)), f32),
                jnp.asarray(rng.standard_normal((k, p)), f32),
                jnp.float32(2.0),
                jnp.asarray(rng.standard_normal((m, p)), f32),
                jnp.asarray(rng.standard_normal(m), f32),
                jnp.asarray(mask),
                jnp.asarray(rng.standard_normal(p), f32),
                0.3, 1.2), {"loss_fn": guarded_loss("quadratic"),
                            "b_steps": 4,
                            "opt": AdamWConfig(lr=0.1, weight_decay=0.0,
                                               grad_clip=0.0,
                                               moment_dtype=jnp.float32)}
    raise NotImplementedError(op)


@pytest.mark.parametrize("op", dispatch.ops())
def test_all_impls_match_reference(op):
    """Acceptance: every registered implementation of every op agrees with
    ``reference`` within 1e-5 on randomized inputs (Pallas via the explicit
    interpret opt-in off-TPU)."""
    args, kw = _make_args(op)
    want = _as_tuple(resolve(op, ReproBackend.using(**{op: "reference"}))(
        *args, **kw))
    for impl in dispatch.implementations(op):
        backend = ReproBackend.using(interpret=True, **{op: impl})
        got = _as_tuple(resolve(op, backend)(*args, **kw))
        assert len(got) == len(want), (op, impl)
        for g, w in zip(got, want):
            np.testing.assert_allclose(
                np.asarray(g, np.float32), np.asarray(w, np.float32),
                atol=1e-5, rtol=1e-5,
                err_msg=f"{op}/{impl} diverges from reference")


@pytest.mark.parametrize("op,seed", [(op, s) for op in ("mix", "sparse_mix",
                                                        "admm_primal")
                                     for s in (1, 2, 3)])
def test_parity_extra_random_draws(op, seed):
    args, kw = _make_args(op, seed=seed)
    want = _as_tuple(resolve(op, ReproBackend.using(**{op: "reference"}))(
        *args, **kw))
    for impl in dispatch.implementations(op):
        got = _as_tuple(resolve(
            op, ReproBackend.using(interpret=True, **{op: impl}))(*args, **kw))
        for g, w in zip(got, want):
            np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                       atol=1e-5, rtol=1e-5)


class TestSelectionRules:
    def test_every_op_has_reference_and_xla(self):
        for op in dispatch.ops():
            impls = dispatch.implementations(op)
            assert "reference" in impls, op
            assert "xla" in impls, op

    def test_auto_never_picks_interpret_silently(self):
        """Off-TPU, auto must resolve to the fused XLA impl, not Pallas
        interpret (the satellite fix: interpret is explicit opt-in only)."""
        if jax.default_backend() == "tpu":
            pytest.skip("auto picks compiled Pallas on TPU by design")
        for op in dispatch.ops():
            fn = resolve(op)
            entry = dispatch._REGISTRY[op]["xla"]
            assert fn is entry.make(False), op

    def test_pallas_off_tpu_requires_explicit_interpret(self):
        if jax.default_backend() == "tpu":
            pytest.skip("Pallas compiles on TPU")
        with pytest.raises(BackendUnavailable):
            resolve("mix", ReproBackend.using(mix="pallas"))
        # explicit opt-in works
        fn = resolve("mix", ReproBackend.using(mix="pallas", interpret=True))
        args, _ = _make_args("mix")
        want = ref.graph_mix(*args)
        np.testing.assert_allclose(np.asarray(fn(*args)), np.asarray(want),
                                   atol=1e-5, rtol=1e-5)

    def test_explicit_interpret_false_beats_env_opt_in(self, monkeypatch):
        """REPRO_PALLAS_INTERPRET=1 in the env never changes what auto
        selects — interpret mode is a property of *how* an explicitly
        requested Pallas impl runs, not a selection preference, so auto
        resolves to fused XLA with or without the env opt-in."""
        if jax.default_backend() == "tpu":
            pytest.skip("off-TPU selection rule")
        monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "1")
        xla = dispatch._REGISTRY["mix"]["xla"].make(False)
        assert resolve("mix", ReproBackend(interpret=False)) is xla
        assert resolve("mix", ReproBackend()) is xla
        # the env opt-in still unlocks an explicitly requested Pallas impl
        fn = resolve("mix", ReproBackend.using(mix="pallas"))
        args, _ = _make_args("mix")
        np.testing.assert_allclose(np.asarray(fn(*args)),
                                   np.asarray(ref.graph_mix(*args)),
                                   atol=1e-5, rtol=1e-5)

    def test_auto_never_picks_interpret_impl_any_platform(self, monkeypatch):
        """The auto rule, pinned for every platform: auto resolution never
        returns a Pallas impl — not in interpret mode (even under the env
        opt-in) and not compiled on TPU either, where it resolves the same
        fused XLA impl as on CPU (no chip measurement justifies a Pallas
        default)."""
        monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "1")
        for platform in ("cpu", "gpu", "tpu"):
            monkeypatch.setattr(dispatch, "_platform", lambda p=platform: p)
            for op in dispatch.ops():
                name = dispatch._auto_impl(op)
                entry = dispatch._REGISTRY[op][name]
                assert not entry.pallas, (platform, op, name)
                assert not entry.interpret_only, (platform, op, name)
                want = ("xla" if "xla" in dispatch.implementations(op)
                        else "reference")
                assert name == want, (platform, op, name)
                assert resolve(op, ReproBackend()) \
                    is dispatch._REGISTRY[op][want].make(False)

    def test_interpret_only_impl_needs_opt_in_everywhere(self, monkeypatch):
        """admm_edge/pallas is interpret-only: unavailable and unresolvable
        without the interpret opt-in even on TPU, still usable as an
        explicit validation target with it."""
        entry = dispatch._REGISTRY["admm_edge"]["pallas"]
        assert entry.interpret_only
        for platform in ("cpu", "tpu"):
            monkeypatch.setattr(dispatch, "_platform", lambda p=platform: p)
            assert not dispatch.available("admm_edge", "pallas",
                                          interpret=False)
            assert dispatch.available("admm_edge", "pallas", interpret=True)
            with pytest.raises(BackendUnavailable):
                resolve("admm_edge", ReproBackend.using(
                    admm_edge="pallas", interpret=False))

    def test_override_and_default_selection(self):
        b = ReproBackend.using(mix="reference")
        assert resolve("mix", b) is ref.graph_mix
        b2 = ReproBackend(default="reference")
        assert resolve("sparse_mix", b2) is ref.sparse_gather_mix

    def test_unknown_op_and_impl_raise(self):
        with pytest.raises(KeyError):
            resolve("no_such_op")
        with pytest.raises(KeyError):
            resolve("mix", ReproBackend.using(mix="no_such_impl"))

    def test_backend_is_hashable_static_arg(self):
        b = ReproBackend.using(mix="xla", interpret=True)
        assert hash(b) == hash(ReproBackend.using(mix="xla", interpret=True))

    def test_register_new_impl(self):
        name = "test_tmp_impl"
        try:
            @dispatch.register("mix", name)
            def _mix_double_checked(theta, theta_sol, A, b):
                return ref.graph_mix(theta, theta_sol, A, b)

            args, _ = _make_args("mix")
            got = resolve("mix", ReproBackend.using(mix=name))(*args)
            np.testing.assert_allclose(np.asarray(got),
                                       np.asarray(ref.graph_mix(*args)),
                                       atol=1e-6)
        finally:
            dispatch._REGISTRY["mix"].pop(name, None)


def test_no_direct_kernel_imports_outside_kernels():
    """Acceptance: production call sites resolve kernels through dispatch —
    no module outside kernels/ imports a concrete kernel module.

    The check itself lives in the AST linter (tools/lint rule RPL001,
    which sees import *nodes* instead of regex-matching source lines);
    this test is a thin wrapper so the invariant still fails loudly in
    plain pytest runs without the CI static-analysis lane."""
    root = pathlib.Path(__file__).resolve().parent.parent
    if str(root) not in sys.path:
        sys.path.insert(0, str(root))
    from tools.lint import lint_paths

    findings = lint_paths(
        [str(root / p) for p in ("src/repro", "benchmarks", "examples")],
        select=["RPL001"], root=str(root))
    offenders = [f.format() for f in findings if not f.waived]
    assert not offenders, f"direct kernel imports: {offenders}"
