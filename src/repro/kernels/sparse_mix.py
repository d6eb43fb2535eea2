"""Pallas TPU kernel: sparse gather-mix — the CSR model-propagation sweep.

Computes, over padded-neighbor tables (DESIGN.md §4),

    out[i] = sum_s w[i, s] * table[idx[i, s]] + b[i] * sol[i]

i.e. one synchronous Eq. (5) sweep of the sparse simulator: each agent mixes
its k_max neighbor models (gathered by index from the stacked model table)
with its anchored solitary model.  This is the O(n k p) counterpart of
``graph_mix.py``'s dense (n x n) @ (n x D) MXU matmul: arithmetic intensity
drops to ~k, so the kernel is gather-bandwidth-bound; the win over the
unfused jnp path (take -> einsum -> fma) is a single pass over the slot
tables with the anchor fused in.

TPU mapping: the agent axis is tiled into blocks of ``block_n`` rows; the
model table stays resident and is gathered row-by-row with dynamic slices
(k_max is small — 8/16 — so the inner slot loop is fully unrolled).

VMEM bound: the whole (N, p) gather table is one VMEM block.  The v5e
compiler (tests/test_chip_compile.py) accepts N=65,536, p=64 (a 16 MiB
table) and refuses N=200,000 (51 MB: over the 16 MiB scoped-VMEM limit)
and N=500,000 (256 MB: over the 128 MiB of VMEM).  The sharded wrapper
all-gathers the whole table first, so the same bound holds for N there.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


DEFAULT_BLOCK_N = 128


def _kernel(idx_ref, w_ref, b_ref, sol_ref, table_ref, out_ref, *, k: int):
    bn = idx_ref.shape[0]

    def row(r, _):
        acc = b_ref[r, 0] * sol_ref[pl.ds(r, 1), :].astype(jnp.float32)
        for s in range(k):                       # k_max static, unrolled
            nbr = table_ref[pl.ds(idx_ref[r, s], 1), :].astype(jnp.float32)
            acc = acc + w_ref[r, s] * nbr
        out_ref[pl.ds(r, 1), :] = acc.astype(out_ref.dtype)  # scatter: unique targets
        return 0

    jax.lax.fori_loop(0, bn, row, 0)


@functools.partial(jax.jit, static_argnames=("block_n", "interpret"))
def sparse_gather_mix(table, idx, w, b, sol, *,
                      block_n: int = DEFAULT_BLOCK_N,
                      interpret: bool = False):
    """table: (N, p); idx: (n, k) int32; w: (n, k); b: (n,); sol: (n, p)
    -> (n, p).

    The output row count follows ``idx``/``sol``; the gather table may hold
    more rows than are mixed (N >= n) — the partitioned engines mix each
    shard's n local rows against the all-gathered N-row global table.

    Pad slots must carry w == 0 (their gathered rows are multiplied away),
    which is exactly the NeighborTables convention.

    ``interpret`` is an explicit opt-in (CPU validation only); the default
    compiles for TPU. Prefer ``kernels.dispatch.resolve("sparse_mix",
    backend)``, which picks the right implementation per platform.
    """
    n_table, p = table.shape
    n, k = idx.shape
    np_ = pl.cdiv(n, block_n) * block_n
    if np_ != n:
        pad = ((0, np_ - n), (0, 0))
        idx_p = jnp.pad(idx, pad)                  # pad rows gather table[0]
        w_p = jnp.pad(w, pad)                      # ... with zero weight
        b_p = jnp.pad(b, (0, np_ - n))
        sol_p = jnp.pad(sol, pad)
    else:
        idx_p, w_p, b_p, sol_p = idx, w, b, sol
    grid = (np_ // block_n,)
    out = pl.pallas_call(
        functools.partial(_kernel, k=k),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_n, k), lambda i: (i, 0)),   # idx tile
            pl.BlockSpec((block_n, k), lambda i: (i, 0)),   # w tile
            pl.BlockSpec((block_n, 1), lambda i: (i, 0)),   # b tile
            pl.BlockSpec((block_n, p), lambda i: (i, 0)),   # sol tile
            pl.BlockSpec((n_table, p), lambda i: (0, 0)),   # table: resident
        ],
        out_specs=pl.BlockSpec((block_n, p), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((np_, p), table.dtype),
        interpret=interpret,
    )(idx_p, w_p, b_p[:, None], sol_p, table)
    return out[:n]
