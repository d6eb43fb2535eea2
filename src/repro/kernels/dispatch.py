"""Unified backend dispatch for the mixing/ADMM hot paths (DESIGN.md §10).

The paper's two algorithms share a handful of hot-path primitives — the
graph-weighted model mix (Eq. 5), its CSR gather-mix counterpart, the
quadratic CL-ADMM primal, the fused ADMM edge update, the per-agent
neighbor reduction, and causal attention for the LM workloads.  Each exists
in up to three realizations (pure-jnp oracle, fused XLA expression, Pallas
TPU kernel); before this module every call site picked one ad-hoc.

This module is the single chooser.  A registry keyed by

    op   ∈ {mix, sparse_mix, admm_primal, admm_edge, round_step,
            cl_edge_step, edge_reweight, neighbor_aggregate, attention}
    impl ∈ {reference, xla, pallas, pallas_sparse}

maps to concrete callables; ``resolve(op, backend)`` returns the callable a
call site should use.  Selection rules:

* **auto** (the default): the fused XLA impl on every platform, TPU
  included (the reference where an op has no XLA impl).  No Pallas impl is
  an auto default: none has a chip measurement showing it beating XLA, and
  the ``round_step``/``cl_edge_step``/``sparse_mix`` kernels keep whole
  state arrays in VMEM, which the v5e compiler refuses at deployment
  sizes (each kernel's docstring states the bound it compiles under).  A
  Pallas impl becomes a default again only with a benchmark cell showing
  it winning on the chip.  Pallas impls run when requested per op — on
  TPU compiled, elsewhere with the explicit interpret opt-in
  (``ReproBackend.using(interpret=True, <op>="pallas")``).  Impls
  registered ``interpret_only=True`` (the ``admm_edge`` Pallas kernel,
  ~36x slower than its fused-XLA form even compiled) require the
  interpret opt-in everywhere.
* per-op **overrides** via :class:`ReproBackend`, threaded through
  ``core.model_propagation`` / ``core.collaborative`` / ``core.sparse`` /
  ``simulate.engines`` / ``coupling.strategies`` / ``models.blocks``.
* env escape hatches for experiments without code changes:
  ``REPRO_BACKEND=<impl>`` forces the default implementation,
  ``REPRO_PALLAS_INTERPRET=1`` opts in to interpret mode off-TPU.
  Both are read at TRACE time: jitted engines whose static backend arg is
  unchanged keep their compiled program, so flipping an env var
  mid-process does not retrace — pass an explicit ``ReproBackend`` to
  switch implementations reliably.

``ReproBackend`` is a frozen (hashable) dataclass so it can ride through
``jax.jit`` static arguments; resolution happens at trace time, so the
chosen implementation is baked into the compiled program.

Registering a new op implementation::

    from repro.kernels import dispatch

    @dispatch.register("mix", "my_impl")
    def _mix_my_impl(theta, theta_sol, A, b):
        ...

Pallas implementations register a *factory* taking the interpret flag::

    @dispatch.register("mix", "my_pallas", pallas=True)
    def _mix_my_pallas(interpret):
        return functools.partial(my_kernel, interpret=interpret)

Every implementation of an op must share the op's canonical signature
(documented per-op below); parity with ``reference`` within 1e-5 on
randomized inputs is enforced by tests/test_dispatch.py.
"""

from __future__ import annotations

import dataclasses
import functools
import os
from typing import Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from . import admm_update as _au
from . import flash_attention as _fa
from . import graph_mix as _gm
from . import ref
from . import round_fuse as _rf
from . import sharded as _sh
from . import sparse_mix as _sm
# Backend-independent slot-table/prefetch helpers for the round_step op,
# re-exported so engine code reaches them through dispatch (the
# no-direct-kernel-imports invariant) — they are layout utilities shared by
# every round_step impl, not selectable implementations themselves.
from .round_fuse import (decode_slots, encode_slots,  # noqa: F401
                         round_prefetch, round_scales, round_stale_src)

IMPLS = ("reference", "xla", "pallas", "pallas_sparse", "xla_sharded",
         "pallas_sparse_sharded")


class BackendUnavailable(RuntimeError):
    """Requested implementation cannot run on this platform as configured."""


@dataclasses.dataclass(frozen=True)
class _Impl:
    """One registered implementation of an op.

    ``make(interpret)`` returns the callable; non-Pallas impls ignore the
    flag.  ``pallas`` marks impls that lower through pallas_call and hence
    need a TPU (compiled) or an explicit interpret opt-in (CPU/GPU).
    ``interpret_only`` marks Pallas impls kept for validation only (their
    compiled form loses to fused XLA): auto never selects them on any
    platform and resolving one requires the interpret opt-in even on TPU.
    """

    name: str
    make: Callable[[bool], Callable]
    pallas: bool = False
    interpret_only: bool = False


_REGISTRY: Dict[str, Dict[str, _Impl]] = {}


def register(op: str, impl: str, *, pallas: bool = False,
             interpret_only: bool = False):
    """Decorator registering ``fn`` as implementation ``impl`` of ``op``.

    Plain impls register the op callable itself; Pallas impls (``pallas=
    True``) register a factory ``make(interpret: bool) -> callable``;
    ``interpret_only=True`` (implies Pallas semantics) demotes the impl to
    an explicit-opt-in validation tool.
    """
    def deco(fn):
        # profiler attribution: every registered hot-path callable runs
        # under a stable "repro/<op>/<impl>" scope, so jax.profiler traces
        # group kernel time by the dispatch decision that produced it
        # (DESIGN.md §14).  Scoping happens here — not in resolve() — so
        # ``make(interpret)`` is memoized and repeated resolution returns
        # the identical callable (jit caches keyed on it stay warm, and
        # selection can be asserted with ``is``).  Reference impls stay
        # unwrapped: they are parity oracles, not profiled hot paths.
        scope = f"repro/{op}/{impl}"

        def _scoped(inner):
            @functools.wraps(inner)
            def run(*args, **kwargs):
                with jax.named_scope(scope):
                    return inner(*args, **kwargs)
            return run

        if impl == "reference":
            make = (lambda interpret, _fn=fn: _fn)
        elif pallas:
            make = functools.lru_cache(maxsize=None)(
                lambda interpret, _fn=fn: _scoped(_fn(interpret)))
        else:
            make = (lambda interpret, _fn=_scoped(fn): _fn)
        _REGISTRY.setdefault(op, {})[impl] = _Impl(impl, make, pallas,
                                                   interpret_only)
        return fn
    return deco


def ops() -> Tuple[str, ...]:
    """All registered op names."""
    return tuple(sorted(_REGISTRY))


def implementations(op: str) -> Tuple[str, ...]:
    """Registered implementation names for ``op`` (reference first)."""
    impls = _REGISTRY[op]
    return tuple(sorted(impls, key=lambda n: (n != "reference", n)))


def _env_default() -> str:
    return os.environ.get("REPRO_BACKEND", "auto")


def _env_interpret() -> bool:
    # same parse as kernels.ops._interpret: set-and-not-falsy means opt-in
    env = os.environ.get("REPRO_PALLAS_INTERPRET")
    return env is not None and env not in ("0", "false", "False")


def _platform() -> str:
    return jax.default_backend()


@dataclasses.dataclass(frozen=True)
class ReproBackend:
    """Backend selection config threaded through the algorithm layers.

    default:   implementation used for every op without an override —
               "auto" picks fused XLA on every platform.
    overrides: per-op (op, impl) pairs, e.g. (("mix", "pallas"),).
    interpret: explicit opt-in to Pallas interpret mode off-TPU (None
               defers to the REPRO_PALLAS_INTERPRET env var; on TPU the
               kernels always compile unless interpret is True).

    Frozen/hashable so it can be a jit static argument.
    """

    default: str = "auto"
    overrides: Tuple[Tuple[str, str], ...] = ()
    interpret: Optional[bool] = None

    @classmethod
    def using(cls, default: str = "auto",
              interpret: Optional[bool] = None, **per_op: str) -> "ReproBackend":
        """Keyword-friendly constructor: ``ReproBackend.using(mix="pallas")``."""
        return cls(default=default,
                   overrides=tuple(sorted(per_op.items())),
                   interpret=interpret)

    def impl_for(self, op: str) -> str:
        for o, impl in self.overrides:
            if o == op:
                return impl
        return self.default

    def wants_interpret(self) -> bool:
        if self.interpret is not None:
            return self.interpret
        return _env_interpret()


def _auto_impl(op: str) -> str:
    """Platform default: the fused XLA impl on every platform (the
    reference where an op has none).  Auto never selects a Pallas impl —
    not compiled on TPU (no chip measurement justifies one as a default)
    and not in interpret mode anywhere (tests/test_dispatch.py pins this
    rule)."""
    return "xla" if "xla" in _REGISTRY[op] else "reference"


def available(op: str, impl: str, *, interpret: Optional[bool] = None) -> bool:
    """Whether (op, impl) can run here. Pallas impls need a TPU or an
    interpret opt-in; interpret-only impls need the opt-in everywhere."""
    entry = _REGISTRY.get(op, {}).get(impl)
    if entry is None:
        return False
    if not entry.pallas:
        return True
    if interpret is None:
        interpret = _env_interpret()
    if entry.interpret_only:
        return bool(interpret)
    return _platform() == "tpu" or bool(interpret)


def resolve(op: str, backend: Optional[ReproBackend] = None) -> Callable:
    """Return the callable implementing ``op`` under ``backend``.

    Happens at trace time (cheap, deterministic): jitted engines bake the
    chosen implementation into the compiled program.
    """
    if op not in _REGISTRY:
        raise KeyError(f"unknown op {op!r}; registered: {ops()}")
    if backend is None:
        backend = ReproBackend(default=_env_default())
    name = backend.impl_for(op)
    if name == "auto":
        name = _auto_impl(op)
    entry = _REGISTRY[op].get(name)
    if entry is None:
        raise KeyError(
            f"op {op!r} has no implementation {name!r}; "
            f"registered: {implementations(op)}")
    interpret = False
    if entry.pallas:
        interpret = backend.wants_interpret()
        if entry.interpret_only and not interpret:
            raise BackendUnavailable(
                f"{op}/{name} is an interpret-only validation kernel (its "
                f"compiled form loses to the fused XLA impl). Pass "
                f"ReproBackend(interpret=True) (or set "
                f"REPRO_PALLAS_INTERPRET=1) to run it, or use the 'xla' "
                f"implementation.")
        if _platform() != "tpu" and not interpret:
            raise BackendUnavailable(
                f"{op}/{name} is a Pallas kernel: it compiles on TPU only. "
                f"On {_platform()!r} pass ReproBackend(interpret=True) (or "
                f"set REPRO_PALLAS_INTERPRET=1) to opt in to the slow "
                f"interpret mode, or use the 'xla' implementation.")
        if _platform() == "tpu" and backend.interpret is None \
                and not entry.interpret_only:
            interpret = False          # compiled is the TPU default
    return entry.make(interpret)


# ---------------------------------------------------------------------------
# mix — dense graph-weighted model mixing (paper Eq. 5):
#   (theta (n, D), theta_sol (n, D), A (n, n), b (n,)) -> (n, D)
#   out = A @ theta + b[:, None] * theta_sol
# ---------------------------------------------------------------------------


register("mix", "reference")(ref.graph_mix)


@register("mix", "xla")
def _mix_xla(theta, theta_sol, A, b):
    """Fused single-pass XLA form (f32 accumulate, MXU-friendly dot)."""
    acc = jnp.dot(A.astype(jnp.float32), theta.astype(jnp.float32),
                  preferred_element_type=jnp.float32)
    return (acc + b.astype(jnp.float32)[:, None]
            * theta_sol.astype(jnp.float32)).astype(theta.dtype)


@register("mix", "pallas", pallas=True)
def _mix_pallas(interpret):
    return functools.partial(_gm.graph_mix, interpret=interpret)


@register("mix", "xla_sharded")
def _mix_xla_sharded(theta, theta_sol, A, b):
    """Row-sharded mix over the sim mesh (all-gathered theta); per-shard
    math is the fused XLA form, so parity with it is exact."""
    return _sh.sharded_graph_mix(theta, theta_sol, A, b, inner=_mix_xla)


# ---------------------------------------------------------------------------
# sparse_mix — CSR gather-mix over padded-neighbor tables:
#   (table (n, p), idx (n, k) int32, w (n, k), b (n,), sol (n, p)) -> (n, p)
#   out[i] = sum_s w[i, s] * table[idx[i, s]] + b[i] * sol[i]
# ---------------------------------------------------------------------------


register("sparse_mix", "reference")(ref.sparse_gather_mix)


@register("sparse_mix", "xla")
def _sparse_mix_xla(table, idx, w, b, sol):
    """Fused take → einsum → fma (the O(n k p) simulator hot loop)."""
    gathered = table[idx].astype(jnp.float32)                # (n, k, p)
    mixed = jnp.einsum("nk,nkp->np", w.astype(jnp.float32), gathered)
    return (mixed + b.astype(jnp.float32)[:, None]
            * sol.astype(jnp.float32)).astype(table.dtype)


@register("sparse_mix", "pallas_sparse", pallas=True)
def _sparse_mix_pallas(interpret):
    return functools.partial(_sm.sparse_gather_mix, interpret=interpret)


@register("sparse_mix", "xla_sharded")
def _sparse_mix_xla_sharded(table, idx, w, b, sol):
    """Agent-sharded gather-mix over the sim mesh: each shard all-gathers
    the model table and runs the fused XLA mix on its row block."""
    return _sh.sharded_sparse_mix(table, idx, w, b, sol,
                                  inner=_sparse_mix_xla)


@register("sparse_mix", "pallas_sparse_sharded", pallas=True)
def _sparse_mix_pallas_sharded(interpret):
    inner = functools.partial(_sm.sparse_gather_mix, interpret=interpret)
    return functools.partial(_sh.sharded_sparse_mix, inner=inner)


# ---------------------------------------------------------------------------
# admm_primal — exact quadratic CL-ADMM primal for one agent's slot row
# (paper §4.2 step 1, block elimination):
#   (w (k,), live (k,) bool, z_own (k, p), z_nbr (k, p), l_own (k, p),
#    l_nbr (k, p), D_l, m_l, sx (p,), mu, rho) -> (theta_l (p,), theta_js (k, p))
# ---------------------------------------------------------------------------


register("admm_primal", "reference")(ref.quadratic_primal)


def _admm_primal_batched_call(fn, w, live, z_own_s, z_nbr_s, l_own_s,
                              l_nbr_s, D_l, m_l, sx, mu, rho):
    """Accept the canonical rowwise signature on a batched impl ``fn`` by
    lifting single-row inputs to a batch of one."""
    one = [a[None] for a in (w, live, z_own_s, z_nbr_s, l_own_s, l_nbr_s)]
    D_b = jnp.asarray(D_l, jnp.float32)[None]
    m_b = jnp.asarray(m_l, jnp.float32)[None]
    theta, theta_js = fn(*one, D_b, m_b, sx[None], mu, rho)
    return theta[0], theta_js[0]


@register("admm_primal", "xla")
def _admm_primal_xla(w, live, z_own_s, z_nbr_s, l_own_s, l_nbr_s,
                     D_l, m_l, sx, mu, rho):
    """Fused XLA form: one masked pass over the slot row, dot-product
    reductions instead of where-sums."""
    f = jnp.float32
    w = w.astype(f)
    wl = jnp.where(live, w, 0.0)                              # (k,)
    b = rho * z_nbr_s.astype(f) - l_nbr_s.astype(f)           # (k, p)
    denom = jnp.where(live, w + rho, 1.0)                     # (k,)
    n_nbrs = jnp.sum(live)
    a = (D_l + 2.0 * mu * D_l * m_l + rho * n_nbrs
         - jnp.sum(wl * wl / denom))
    zo = jnp.where(live[:, None], rho * z_own_s.astype(f)
                   - l_own_s.astype(f), 0.0)
    rhs = (2.0 * mu * D_l * sx
           + jnp.sum(zo, axis=0)
           + (wl / denom) @ jnp.where(live[:, None], b, 0.0))
    theta_l = rhs / a
    theta_js = (w[:, None] * theta_l[None, :] + b) / denom[:, None]
    return theta_l, theta_js


@register("admm_primal", "xla_sharded")
def _admm_primal_xla_sharded(w, live, z_own_s, z_nbr_s, l_own_s, l_nbr_s,
                             D_l, m_l, sx, mu, rho):
    """Agent-row-sharded primal over the sim mesh (per-shard vmap of the
    fused XLA row solve — the solve is row-local, so no collective).

    Accepts the canonical rowwise signature AND the stacked batched form
    ((n, k), ... (n,), (n, p)); ``core.sparse.batched_admm_primal`` feeds
    sharded impls the batched form directly instead of vmapping them.
    """
    run = functools.partial(_sh.sharded_admm_primal, inner=_admm_primal_xla)
    if w.ndim == 1:
        return _admm_primal_batched_call(
            run, w, live, z_own_s, z_nbr_s, l_own_s, l_nbr_s,
            D_l, m_l, sx, mu, rho)
    return run(w, live, z_own_s, z_nbr_s, l_own_s, l_nbr_s, D_l, m_l, sx,
               mu, rho)


# ---------------------------------------------------------------------------
# admm_primal_inexact — B AdamW steps on the reduced local Lagrangian
# (DiNNO-style inexact primal for arbitrary differentiable losses,
# DESIGN.md §18).  Canonical rowwise signature:
#   (w (k,), live (k,) bool, z_own (k, p), z_nbr (k, p), l_own (k, p),
#    l_nbr (k, p), D_l, x (m, q), y (m,), mask (m,), theta0 (p,), mu, rho,
#    *, loss_fn, b_steps, opt) -> (theta_l (p,), theta_js (k, p))
# loss_fn / b_steps / opt are trace-time constants supplied by the
# PrimalSolver (core.primal.InexactPrimal), which vmaps the row op over
# the round's compacted agent rows; b_steps=None is the provable B -> inf
# fixed point (quadratic loss only — the exact quadratic_primal solve).
# ---------------------------------------------------------------------------


register("admm_primal_inexact", "reference")(ref.inexact_primal)
# the reference is already a fused scan of AdamW steps; CPU/GPU reuse it
register("admm_primal_inexact", "xla")(ref.inexact_primal)


# ---------------------------------------------------------------------------
# admm_edge — fused CL-ADMM Z + dual update for a batch of edges
# (paper §4.2 steps 2-3): 8 inputs (E, p), rho kw-only -> 6 outputs (E, p)
# ---------------------------------------------------------------------------


register("admm_edge", "reference")(ref.admm_edge_update)


@register("admm_edge", "xla")
def _admm_edge_xla(t_ii, t_ji, t_jj, t_ij, l_own_i, l_nbr_j_of_i,
                   l_own_j, l_nbr_i_of_j, *, rho: float):
    return ref.admm_edge_update(t_ii, t_ji, t_jj, t_ij, l_own_i,
                                l_nbr_j_of_i, l_own_j, l_nbr_i_of_j, rho)


@register("admm_edge", "xla_sharded")
def _admm_edge_xla_sharded(t_ii, t_ji, t_jj, t_ij, l_own_i, l_nbr_j_of_i,
                           l_own_j, l_nbr_i_of_j, *, rho: float):
    """Edge-axis-sharded Z/dual update over the sim mesh; per-shard math is
    the reference expression, so parity with it is exact."""
    return _sh.sharded_admm_edge(t_ii, t_ji, t_jj, t_ij, l_own_i,
                                 l_nbr_j_of_i, l_own_j, l_nbr_i_of_j,
                                 rho=rho, inner=ref.admm_edge_update)


# Interpret-only: the compiled form of this kernel is ~36x slower than the
# fused XLA expression (BENCH_dispatch) — it stays registered as a parity
# target for the Pallas gather/scatter idiom, never as a hot path.
@register("admm_edge", "pallas", pallas=True, interpret_only=True)
def _admm_edge_pallas(interpret):
    return functools.partial(_au.admm_edge_update, interpret=interpret)


# ---------------------------------------------------------------------------
# round_step — one fused MP gossip round (scenario-engine semantics) over
# the flat slot table (round_fuse module docstring, DESIGN.md §15):
#   (theta (n,p), Ke (n*k, p+1) slots + id column, got_ever (n,) bool,
#    msg (2B,p), tgt_row (2B,) int32, enc (2B,) int32, k_old (2B,p),
#    theta_base (n,p), a_w (n*k,)) -> (theta', Ke', got_ever', keep (2B,))
# Event operands come from ``round_fuse.round_prefetch`` (gathered *after*
# the previous round's scatters); ``Ke``/``a_w``/``theta_base`` come from
# ``encode_slots`` / ``round_scales`` / the Eq. 6 image of the warm-start
# slots.  The op assumes the scheduler's delivery => active-receiver
# guarantee and never consults an ``active`` vector.
# ---------------------------------------------------------------------------


register("round_step", "reference")(ref.gossip_round_step)
register("round_step", "xla")(_rf.round_step_xla)


@register("round_step", "pallas", pallas=True)
def _round_step_pallas(interpret):
    return functools.partial(_rf.round_step_pallas, interpret=interpret)


# ---------------------------------------------------------------------------
# cl_edge_step — one fused CL-ADMM edge phase (scenario-engine semantics):
#   (theta (n,p), K (n,k,p), Z_own, Z_nbr, L_own, L_nbr (n,k,p),
#    pv_th/pv_K/pv_Lo/pv_Ln (E,p) previous round's publish at the partner
#    cells, f_Lo/f_Ln (E,p) round-start partner duals,
#    upd/own_s/oth_a/oth_s (E,) int32, stale/got (E,) bool, *, rho)
#   -> (Z_own', Z_nbr', L_own', L_nbr')
# The payload rows come from ``simulate.engines._cl_scenario_scan``, which
# gathers them after the scatters of the rounds before, so no whole-state
# snapshot stays live across a round.
# ---------------------------------------------------------------------------


register("cl_edge_step", "reference")(_rf.cl_edge_step)
# The masked gather/halfstep/scatter expression already lowers to one fused
# XLA program; registering the identical callable keeps the scenario
# engine's trajectory bit-for-bit whichever name resolves (same precedent
# as edge_reweight).
register("cl_edge_step", "xla")(_rf.cl_edge_step)


@register("cl_edge_step", "pallas", pallas=True)
def _cl_edge_step_pallas(interpret):
    return functools.partial(_rf.cl_edge_step_pallas, interpret=interpret)


# ---------------------------------------------------------------------------
# edge_reweight — local collaboration-graph re-estimation (Zantedeschi et
# al. 2019): sparse simplex projection of per-slot dissimilarities, blended
# into the current row-stochastic weights:
#   (d (B, k), w (B, k), live (B, k) bool, *, eta, lam) -> (B, k)
#   out = (1 - eta) * w + eta * proj_simplex(-d / (2 lam), live)
# ---------------------------------------------------------------------------


register("edge_reweight", "reference")(ref.edge_reweight)
# The sort/cumsum projection already lowers to one fused XLA program; the
# reference expression IS the fused form (same precedent as
# neighbor_aggregate), and registering the identical callable keeps the
# joint engines' bit-for-bit trajectory match intact whichever name
# resolves.
register("edge_reweight", "xla")(ref.edge_reweight)


@register("edge_reweight", "xla_sharded")
def _edge_reweight_xla_sharded(d, w, live, *, eta: float, lam: float):
    """Agent-row-sharded re-weighting over the sim mesh (the projection is
    row-local, so no collective); per-shard math is the reference
    expression, so parity with it is exact."""
    return _sh.sharded_edge_reweight(d, w, live, eta=eta, lam=lam,
                                     inner=ref.edge_reweight)


# ---------------------------------------------------------------------------
# neighbor_aggregate — per-agent slot reduction shared by the dense and
# sparse engines:  (w (k,), theta (k, p)) -> (p,)
# ---------------------------------------------------------------------------


register("neighbor_aggregate", "reference")(ref.neighbor_aggregate)
# The einsum IS the fused XLA form; registering the same callable keeps the
# dense/sparse engines' bit-for-bit trajectory match (identical HLO) intact
# whichever name resolves.
register("neighbor_aggregate", "xla")(ref.neighbor_aggregate)


# ---------------------------------------------------------------------------
# attention — causal (optionally sliding-window) attention with GQA
# expansion:  (q (B,S,H,hd), k (B,S,K,hd), v (B,S,K,hd), *, window) -> (B,S,H,hd)
# ---------------------------------------------------------------------------


def _gqa_expand(q, k, v):
    H, K = q.shape[2], k.shape[2]
    if K != H:
        k = jnp.repeat(k, H // K, axis=2)
        v = jnp.repeat(v, H // K, axis=2)
    return k, v


@register("attention", "reference")
def _attention_reference(q, k, v, *, window=None):
    k, v = _gqa_expand(q, k, v)
    return ref.flash_attention(q, k, v, window=window)


# Dense softmax attention lowers to fused XLA ops directly; the reference
# expression is the XLA path.
register("attention", "xla")(_attention_reference)


@register("attention", "pallas", pallas=True)
def _attention_pallas(interpret):
    def run(q, k, v, *, window=None, block_q: int = 256, block_k: int = 256):
        k, v = _gqa_expand(q, k, v)
        return _fa.flash_attention(q, k, v, window=window, block_q=block_q,
                                   block_k=block_k, interpret=interpret)
    return run
