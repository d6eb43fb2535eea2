"""Jit'd wrappers that ALWAYS run the Pallas kernels (kernel validation).

Used by tests/benchmarks that exercise the kernels themselves.  The caller
says how the kernel runs: ``interpret=False`` compiles it (TPU only),
``interpret=True`` runs the slow interpreter so the kernel code path stays
testable on CPU.  Production call sites go through ``kernels.dispatch``
instead, which resolves fused XLA unless a Pallas impl is explicitly
requested.  GQA head expansion for flash_attention happens here so the
kernel sees equal head counts.
"""

from __future__ import annotations

from typing import Optional

import jax.numpy as jnp

from . import graph_mix as _gm
from . import flash_attention as _fa
from . import admm_update as _au
from . import sparse_mix as _sm


def graph_mix(theta, theta_sol, A, b, *, interpret: bool,
              block_d: int = _gm.DEFAULT_BLOCK_D):
    return _gm.graph_mix(theta, theta_sol, A, b, block_d=block_d,
                         interpret=interpret)


def sparse_gather_mix(table, idx, w, b, sol, *, interpret: bool,
                      block_n: int = _sm.DEFAULT_BLOCK_N):
    return _sm.sparse_gather_mix(table, idx, w, b, sol, block_n=block_n,
                                 interpret=interpret)


def flash_attention(q, k, v, *, interpret: bool,
                    window: Optional[int] = None,
                    block_q: int = 256, block_k: int = 256):
    """q: (B, S, H, hd); k, v: (B, S, K, hd) with K | H (GQA)."""
    H, K = q.shape[2], k.shape[2]
    if K != H:
        k = jnp.repeat(k, H // K, axis=2)
        v = jnp.repeat(v, H // K, axis=2)
    return _fa.flash_attention(q, k, v, window=window, block_q=block_q,
                               block_k=block_k, interpret=interpret)


def admm_edge_update(*args, rho: float, interpret: bool, block_e: int = 8,
                     block_p: int = 512):
    return _au.admm_edge_update(*args, rho=rho, block_e=block_e,
                                block_p=block_p, interpret=interpret)
