"""Multi-device (shard_map) wrappers for the mixing hot paths (DESIGN.md §11).

Row-partition the agent axis of a mix op across a 1-D sim mesh
(``launch.sim_mesh``): every shard owns a contiguous block of output rows,
all-gathers the model table its gathers read from, and runs one of the
existing single-device implementations (fused XLA or the Pallas kernel) on
its block.  The wrappers are shape-preserving — global arrays in, global
arrays out — so they register in ``kernels.dispatch`` as ordinary
implementations (``xla_sharded`` / ``pallas_sparse_sharded``) and engine
code stays backend-agnostic.

This is the *graph-oblivious* sharding seam: it cannot know which rows a
shard actually needs, so it exchanges the full table every call.  The
event-driven engines in ``repro.simulate.partition`` sit above this seam
and do better — they precompute a graph partition and exchange only the
halo (boundary) rows.

On a mesh of one device the wrappers degenerate to the inner impl plus a
no-op collective, so they are safe defaults anywhere.
"""

from __future__ import annotations

import math
from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.launch.sim_mesh import AGENT_AXIS, make_sim_mesh, mesh_shards


def _pad_rows(x, rows: int):
    if x.shape[0] == rows:
        return x
    return jnp.pad(x, ((0, rows - x.shape[0]),) + ((0, 0),) * (x.ndim - 1))


def sharded_sparse_mix(table, idx, w, b, sol, *, inner: Callable, mesh=None):
    """CSR gather-mix with the agent axis sharded over the sim mesh.

    table, sol: (n, p); idx: (n, k); w: (n, k); b: (n,) -> (n, p).
    Each shard all-gathers the model table (gather targets are arbitrary
    rows), then runs ``inner`` — any single-device sparse_mix impl — on its
    row block.  Pad rows carry w == 0 / b == 0, so they mix to 0 and are
    sliced off.
    """
    mesh = make_sim_mesh() if mesh is None else mesh
    n = table.shape[0]
    rows = mesh_shards(mesh) * math.ceil(n / mesh_shards(mesh))

    def block(table_blk, idx_blk, w_blk, b_blk, sol_blk):
        full = jax.lax.all_gather(table_blk, AGENT_AXIS, tiled=True)
        return inner(full, idx_blk, w_blk, b_blk, sol_blk)

    spec = P(AGENT_AXIS)
    run = jax.shard_map(block, mesh=mesh, check_vma=False,
                        in_specs=(spec,) * 5, out_specs=spec)
    padded = [_pad_rows(a, rows) for a in (table, idx, w, b, sol)]
    return run(*padded)[:n]


def sharded_admm_primal(
    w, live, z_own, z_nbr, l_own, l_nbr, D, m, sx, mu, rho, *, inner, mesh=None
):
    """Batched quadratic CL-ADMM primal with the agent axis sharded.

    w, live: (n, k); z/l rows: (n, k, p); D, m: (n,); sx: (n, p) ->
    (theta (n, p), theta_js (n, k, p)).  The primal is embarrassingly
    row-parallel (each agent's solve reads only its own slot row), so no
    collective is needed: every shard vmaps ``inner`` — any single-row
    admm_primal impl — over its row block.  Pad rows carry D == 1 and an
    all-False live mask so their (discarded) solves stay finite.
    """
    mesh = make_sim_mesh() if mesh is None else mesh
    n = w.shape[0]
    rows = mesh_shards(mesh) * math.ceil(n / mesh_shards(mesh))

    def row_solve(w_, lv, zo, zn, lo, ln, D_, m_, sx_):
        return inner(w_, lv, zo, zn, lo, ln, D_, m_, sx_, mu, rho)

    spec = P(AGENT_AXIS)
    run = jax.shard_map(
        jax.vmap(row_solve), mesh=mesh, check_vma=False,
        in_specs=(spec,) * 9, out_specs=(spec, spec))
    D_pad = jnp.pad(D, (0, rows - n), constant_values=1.0)
    padded = [_pad_rows(a, rows) for a in (w, live, z_own, z_nbr, l_own, l_nbr)]
    theta, theta_js = run(*padded, D_pad, _pad_rows(m, rows), _pad_rows(sx, rows))
    return theta[:n], theta_js[:n]


def sharded_admm_edge(
    t_ii,
    t_ji,
    t_jj,
    t_ij,
    l_own_i,
    l_nbr_j_of_i,
    l_own_j,
    l_nbr_i_of_j,
    *,
    rho,
    inner,
    mesh=None,
):
    """Fused CL-ADMM Z + dual edge update with the edge axis sharded.

    Eight (E, p) inputs -> six (E, p) outputs, signature-identical to the
    single-device admm_edge impls; each shard runs ``inner`` on its edge
    block (the update is independent per edge, so no collective).
    """
    mesh = make_sim_mesh() if mesh is None else mesh
    n_edges = t_ii.shape[0]
    rows = mesh_shards(mesh) * math.ceil(n_edges / mesh_shards(mesh))

    def block(*args):
        return inner(*args, rho=rho)

    spec = P(AGENT_AXIS)
    run = jax.shard_map(block, mesh=mesh, check_vma=False,
                        in_specs=(spec,) * 8, out_specs=(spec,) * 6)
    padded = [
        _pad_rows(a, rows)
        for a in (t_ii, t_ji, t_jj, t_ij, l_own_i, l_nbr_j_of_i, l_own_j, l_nbr_i_of_j)
    ]
    return tuple(out[:n_edges] for out in run(*padded))


def sharded_edge_reweight(d, w, live, *, eta, lam, inner: Callable, mesh=None):
    """Collaboration-graph re-estimation with the agent (row) axis sharded.

    d, w: (n, k); live: (n, k) bool -> (n, k).  The simplex projection is
    row-local (each agent re-estimates only its own outgoing weights), so
    no collective is needed: every shard runs ``inner`` — any single-device
    edge_reweight impl — on its row block.  Pad rows carry an all-False
    live mask and come back all-zero.
    """
    mesh = make_sim_mesh() if mesh is None else mesh
    n = d.shape[0]
    rows = mesh_shards(mesh) * math.ceil(n / mesh_shards(mesh))

    def block(d_blk, w_blk, live_blk):
        return inner(d_blk, w_blk, live_blk, eta=eta, lam=lam)

    spec = P(AGENT_AXIS)
    run = jax.shard_map(block, mesh=mesh, check_vma=False,
                        in_specs=(spec,) * 3, out_specs=spec)
    padded = [_pad_rows(a, rows) for a in (d, w, live)]
    return run(*padded)[:n]


def sharded_graph_mix(theta, theta_sol, A, b, *, inner: Callable, mesh=None):
    """Dense Eq. (5) mix with the agent (row) axis sharded over the sim mesh.

    theta, theta_sol: (n, D); A: (n, n); b: (n,) -> (n, D).
    A is row-sharded; theta is all-gathered so every shard can form its
    A_blk @ theta product.  Zero pad columns of A mean the pad rows of the
    gathered theta contribute nothing.
    """
    mesh = make_sim_mesh() if mesh is None else mesh
    n = theta.shape[0]
    rows = mesh_shards(mesh) * math.ceil(n / mesh_shards(mesh))
    A_pad = jnp.pad(A, ((0, rows - n), (0, rows - n)))

    def block(theta_blk, sol_blk, A_blk, b_blk):
        full = jax.lax.all_gather(theta_blk, AGENT_AXIS, tiled=True)
        return inner(full, sol_blk, A_blk, b_blk)

    spec = P(AGENT_AXIS)
    run = jax.shard_map(block, mesh=mesh, check_vma=False,
                        in_specs=(spec,) * 4, out_specs=spec)
    padded = [_pad_rows(a, rows) for a in (theta, theta_sol, A_pad, b)]
    return run(*padded)[:n]
