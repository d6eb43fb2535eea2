"""The paper's algorithms as cross-agent coupling strategies at pod scale.

Each data-parallel row of the mesh is an *agent* with personalized parameters
(leading agent dim A on every leaf). After local optimizer updates, a coupling
strategy mixes parameters across the agent axis:

  mode="none"       solitary training (paper Eq. 1 baseline)
  mode="consensus"  uniform averaging == gradient all-reduce fixed point
                    (paper Eq. 2 baseline — what the paper argues *against*)
  mode="mp"         model propagation: one Eq. (5) iterate per application,
                    anchored at a maintained "solitary" snapshot with
                    per-agent confidences (paper §3)
  mode="cl"         collaborative learning: the Q_CL coupling term (paper §4).
                    Default realization is a Laplacian proximal pull
                    (exact gradient of the smoothness term); the full
                    ADMM realization with per-edge Z/Lambda state is
                    available as ``cl_admm`` (costs 4x edge-param memory).

Two communication schedules realize the SAME mixing operator (DESIGN.md §2):

  schedule="dense"   einsum over the agent axis -> XLA lowers to all-gather.
                     This is the paper-faithful *synchronous* operator.
  schedule="gossip"  the paper's pairwise-exchange pattern: the graph is
                     edge-colored into matchings; each matching is executed
                     as paired collective_permutes and partial sums are
                     accumulated — after cycling all matchings the result
                     EQUALS the dense operator (tests/test_coupling.py),
                     but no all-gather ever materializes: peak comm buffer
                     is one neighbor slice instead of A-1.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.core.graph import Graph
from repro.kernels.dispatch import ReproBackend, resolve


@dataclasses.dataclass(frozen=True)
class CouplingConfig:
    mode: str = "mp"              # none | consensus | mp | cl | cl_admm
    schedule: str = "dense"       # dense | gossip
    alpha: float = 0.99           # MP trade-off (mu = (1-alpha)/alpha)
    mu: float = 0.01              # CL trade-off
    rho: float = 1.0              # ADMM penalty
    every: int = 1                # apply every k optimizer steps
    use_kernel: bool = False      # deprecated: request the Pallas "mix" impl
    mix_dtype: Any = jnp.float32  # wire dtype for cross-agent traffic
    # kernels.dispatch.ReproBackend choosing the "mix" implementation
    # (None = auto: fused XLA on every platform)
    backend: Optional[ReproBackend] = None

    def mix_backend(self) -> Optional[ReproBackend]:
        if self.backend is not None:
            return self.backend
        if self.use_kernel:
            return ReproBackend.using(mix="pallas")
        return None


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class CouplingState:
    """Per-run mixing operators (device-resident pytree).

    The gossip matching schedule (``send_to``) is *static* host data — it
    parameterizes collective_permute patterns, which must be known at trace
    time — so it lives in metadata, not as an array leaf.
    """
    A_mix: jnp.ndarray            # (A, A)  diag(alpha/(alpha+abar c)) P  (mp)
    b_anchor: jnp.ndarray         # (A,)    abar c / (alpha + abar c)     (mp)
    W: jnp.ndarray                # (A, A)  raw weights (cl)
    # (M, A) int32 host array: partner id per matching round (-1 = idle)
    send_to: tuple = dataclasses.field(metadata=dict(static=True),
                                       default=())


def mp_matrices(graph: Graph, confidences, alpha: float):
    """Eq. (5) as out = A_mix @ theta + b_anchor * theta_sol."""
    c = np.asarray(confidences, np.float64)
    abar = 1.0 - alpha
    denom = alpha + abar * c
    A_mix = (alpha / denom)[:, None] * np.asarray(graph.P)
    b = abar * c / denom
    return A_mix.astype(np.float32), b.astype(np.float32)


def make_state(graph: Graph, confidences=None, alpha: float = 0.99) -> CouplingState:
    n = graph.n
    if confidences is None:
        confidences = np.ones(n)
    A_mix, b = mp_matrices(graph, confidences, alpha)
    matchings = graph.edge_coloring()
    send_to = np.full((len(matchings), n), -1, np.int32)
    for m, pairs in enumerate(matchings):
        for (i, j) in pairs:
            send_to[m, i] = j
            send_to[m, j] = i
    return CouplingState(
        A_mix=jnp.asarray(A_mix), b_anchor=jnp.asarray(b),
        W=jnp.asarray(graph.W, jnp.float32),
        send_to=tuple(map(tuple, send_to.tolist())))


# ---------------------------------------------------------------------------
# Mixing operators over (A, ...) stacked pytrees
# ---------------------------------------------------------------------------


def _per_leaf(fn, *trees):
    return jax.tree_util.tree_map(fn, *trees)


def dense_mix_tree(params, solitary, state: CouplingState,
                   cfg: CouplingConfig):
    """out = A_mix @ theta + b * theta_sol per leaf, via the "mix" op.

    The implementation (fused XLA einsum, Pallas kernel compiled or
    interpret) is resolved through ``kernels.dispatch`` from
    ``cfg.backend`` — platform auto when None.  All operands (including
    A_mix) are quantized to ``cfg.mix_dtype`` as the wire format; the
    impls accumulate in float32.
    """
    A_mix = state.A_mix.astype(cfg.mix_dtype)
    b = state.b_anchor
    mix_fn = resolve("mix", cfg.mix_backend())

    def mix(leaf, sol):
        n = leaf.shape[0]
        out = mix_fn(leaf.reshape(n, -1).astype(cfg.mix_dtype),
                     sol.reshape(n, -1).astype(cfg.mix_dtype),
                     A_mix, b)
        return out.reshape(leaf.shape).astype(leaf.dtype)

    return _per_leaf(mix, params, solitary)


def gossip_mix_tree(params, solitary, state: CouplingState,
                    cfg: CouplingConfig, axis_names: Tuple[str, ...]):
    """Same operator as dense_mix_tree, via matching-scheduled ppermute.

    Must be called INSIDE shard_map over ``axis_names`` (the agent axes) with
    per-agent slices (leading dim 1 stripped by the caller). Accumulates
    sum_j A_mix[i, j] theta_j one matching at a time; no all-gather.
    """
    send_to = np.asarray(state.send_to, np.int32)  # (M, A) static
    M, A = send_to.shape
    idx = jax.lax.axis_index(axis_names)

    def mix(leaf, sol):
        acc = state.A_mix[idx, idx] * leaf.astype(cfg.mix_dtype)  # self term
        for m in range(M):
            partner = send_to[m]                   # (A,) static int32
            perm = [(int(s), int(d)) for s, d in enumerate(partner) if d >= 0]
            if not perm:
                continue
            recv = jax.lax.ppermute(leaf.astype(cfg.mix_dtype),
                                    axis_name=axis_names, perm=perm)
            pvec = jnp.asarray(partner)
            w = state.A_mix[idx, pvec[idx]]
            w = jnp.where(pvec[idx] >= 0, w, 0.0)
            acc = acc + w * recv
        anchored = state.b_anchor[idx] * sol.astype(cfg.mix_dtype)
        return (acc + anchored).astype(leaf.dtype)

    return _per_leaf(mix, params, solitary)


def consensus_mean_tree(params, cfg: CouplingConfig):
    """Uniform average over the agent axis (Eq. 2 baseline)."""
    def mix(leaf):
        return jnp.broadcast_to(
            jnp.mean(leaf.astype(cfg.mix_dtype), axis=0, keepdims=True,
                     dtype=jnp.float32),
            leaf.shape).astype(leaf.dtype)
    return _per_leaf(mix, params)


def laplacian_pull_tree(params, state: CouplingState, cfg: CouplingConfig,
                        lr: float):
    """CL smoothness-term gradient step (paper §4 objective, SGD realization):

        theta_i <- theta_i - lr * 2 sum_j W_ij (theta_i - theta_j)

    Exactly the gradient of sum_{i<j} W_ij ||theta_i - theta_j||^2. Combined
    with the local-loss optimizer step this is decentralized SGD on Q_CL.
    """
    W = state.W.astype(cfg.mix_dtype)
    deg = W.sum(axis=1, dtype=jnp.float32)

    def mix(leaf):
        lf = leaf.astype(cfg.mix_dtype)
        nbr = jnp.einsum("ab,b...->a...", W, lf,
                         preferred_element_type=jnp.float32)
        grad = 2.0 * (deg.reshape((-1,) + (1,) * (leaf.ndim - 1)) * lf - nbr)
        return (lf - lr * grad).astype(leaf.dtype)

    return _per_leaf(mix, params)


# ---------------------------------------------------------------------------
# Strategy factory
# ---------------------------------------------------------------------------


def make_coupling(cfg: CouplingConfig, state: CouplingState,
                  axis_names: Tuple[str, ...] = ("pod", "data"),
                  mesh=None, param_specs=None):
    """Returns apply(params, solitary, step) -> params.

    ``schedule="gossip"`` wraps the matching rounds in shard_map over the
    agent axes of ``mesh`` (required). ``param_specs`` (stacked
    PartitionSpec tree, agent axis leading) keeps tensor-parallel dims local
    inside the shard_map — without it leaves are assumed replicated beyond
    the agent axis. "dense" works under plain jit/GSPMD.
    """
    if cfg.mode == "none":
        return lambda params, solitary, step: params

    if cfg.mode == "consensus":
        def apply_consensus(params, solitary, step):
            do = (step % cfg.every) == 0
            mixed = consensus_mean_tree(params, cfg)
            return jax.tree_util.tree_map(
                lambda a, b: jnp.where(do, a, b), mixed, params)
        return apply_consensus

    if cfg.mode == "cl":
        def apply_cl(params, solitary, step):
            do = (step % cfg.every) == 0
            # lr folded into mu: proximal step size on the smoothness term
            mixed = laplacian_pull_tree(params, state, cfg, lr=cfg.mu)
            return jax.tree_util.tree_map(
                lambda a, b: jnp.where(do, a, b), mixed, params)
        return apply_cl

    if cfg.mode == "mp":
        if cfg.schedule == "gossip":
            if mesh is None:
                raise ValueError("gossip schedule needs a mesh")
            names = tuple(a for a in axis_names if a in mesh.axis_names)

            def apply_gossip(params, solitary, step):
                if param_specs is not None:
                    specs_in = param_specs
                else:
                    specs_in = jax.tree_util.tree_map(
                        lambda l: P(names, *([None] * (l.ndim - 1))), params)

                def body(p_slice, s_slice):
                    p_loc = jax.tree_util.tree_map(lambda a: a[0], p_slice)
                    s_loc = jax.tree_util.tree_map(lambda a: a[0], s_slice)
                    out = gossip_mix_tree(p_loc, s_loc, state, cfg, names)
                    return jax.tree_util.tree_map(lambda a: a[None], out)

                mixed = jax.shard_map(
                    body, mesh=mesh, in_specs=(specs_in, specs_in),
                    out_specs=specs_in, check_vma=False)(params, solitary)
                do = (step % cfg.every) == 0
                return jax.tree_util.tree_map(
                    lambda a, b: jnp.where(do, a, b), mixed, params)
            return apply_gossip

        def apply_dense(params, solitary, step):
            do = (step % cfg.every) == 0
            mixed = dense_mix_tree(params, solitary, state, cfg)
            return jax.tree_util.tree_map(
                lambda a, b: jnp.where(do, a, b), mixed, params)
        return apply_dense

    raise ValueError(f"unknown coupling mode {cfg.mode!r}")
