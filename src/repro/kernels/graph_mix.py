"""Pallas TPU kernel: fused graph-weighted model mixing (the MP step).

Computes  out = A @ theta + b[:, None] * theta_sol  for stacked agent models
theta (n, D) where D is a flattened parameter block. This is the paper's
model-propagation update (Eq. 5/6) applied blockwise over a large parameter
vector — the compute hot-spot of the coupling layer (DESIGN.md §3).

TPU mapping: n (the agent count) is small (16/32 at pod scale, O(100) in the
paper's setting) and is padded to the 128-lane MXU width once; the parameter
axis D is tiled into VMEM-resident blocks. Each grid step does one
(n x n) @ (n x bD) MXU matmul plus a fused multiply-add — arithmetic
intensity ~n, so the kernel is HBM-bandwidth-bound and the win over the
unfused reference is one pass over theta/theta_sol instead of three.

VMEM bound: A (n, n) stays resident beside (n, block_d) tiles, so n is the
limit and D is not; the v5e compiler (tests/test_chip_compile.py) accepts
n=16, D=4096 and n=256, D=65,536.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


DEFAULT_BLOCK_D = 512


def _kernel(a_ref, b_ref, theta_ref, sol_ref, out_ref):
    A = a_ref[...].astype(jnp.float32)            # (n, n)
    bvec = b_ref[...].astype(jnp.float32)         # (n, 1)
    th = theta_ref[...].astype(jnp.float32)       # (n, bD)
    sol = sol_ref[...].astype(jnp.float32)        # (n, bD)
    mixed = jnp.dot(A, th, preferred_element_type=jnp.float32)
    out_ref[...] = (mixed + bvec * sol).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_d", "interpret"))
def graph_mix(theta, theta_sol, A, b, *, block_d: int = DEFAULT_BLOCK_D,
              interpret: bool = False):
    """theta, theta_sol: (n, D); A: (n, n); b: (n,) -> (n, D).

    ``interpret`` is an explicit opt-in (CPU validation only — orders of
    magnitude slower than the compiled kernel); the default compiles for
    TPU. Prefer ``kernels.dispatch.resolve("mix", backend)``, which picks
    the right implementation per platform.

    D is padded to a multiple of ``block_d`` (lane-aligned); n rides in the
    sublane dim and may be any size (the compiler pads to 8/16/32 sublanes).
    """
    n, D = theta.shape
    Dp = pl.cdiv(D, block_d) * block_d
    if Dp != D:
        pad = ((0, 0), (0, Dp - D))
        theta_p = jnp.pad(theta, pad)
        sol_p = jnp.pad(theta_sol, pad)
    else:
        theta_p, sol_p = theta, theta_sol
    grid = (Dp // block_d,)
    out = pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((n, n), lambda i: (0, 0)),        # A: resident
            pl.BlockSpec((n, 1), lambda i: (0, 0)),        # b
            pl.BlockSpec((n, block_d), lambda i: (0, i)),  # theta tile
            pl.BlockSpec((n, block_d), lambda i: (0, i)),  # sol tile
        ],
        out_specs=pl.BlockSpec((n, block_d), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((n, Dp), theta.dtype),
        interpret=interpret,
    )(A, b[:, None], theta_p, sol_p)
    return out[:, :D]
