"""Kernel micro-benchmarks: Pallas vs jnp oracle — correctness columns +
call timing.  The kernels compile natively on a TPU and run in the Pallas
interpreter elsewhere (admm_edge is interpret-only everywhere); the derived
column reports max |err| vs the oracle and ``interp=`` says which ran, so an
interpreter wall-time is never read as kernel time."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import ops, ref
from .common import time_call, emit


def main(fast: bool = True):
    key = jax.random.PRNGKey(0)
    interpret = jax.default_backend() != "tpu"

    n, D = 16, 4096 if fast else 65536
    k1, k2, k3, k4 = jax.random.split(key, 4)
    theta = jax.random.normal(k1, (n, D))
    sol = jax.random.normal(k2, (n, D))
    A = jax.random.uniform(k3, (n, n)) / n
    b = jax.random.uniform(k4, (n,))
    got = ops.graph_mix(theta, sol, A, b, interpret=interpret)
    want = ref.graph_mix(theta, sol, A, b)
    err = float(jnp.abs(got - want).max())
    us = time_call(ops.graph_mix, theta, sol, A, b, interpret=interpret,
                   sync=jax.block_until_ready)
    emit("kernel_graph_mix", us, f"maxerr={err:.2e} interp={interpret}")

    B, S, H, hd = 1, 256, 2, 64
    q = jax.random.normal(k1, (B, S, H, hd))
    kk = jax.random.normal(k2, (B, S, H, hd))
    v = jax.random.normal(k3, (B, S, H, hd))
    got = ops.flash_attention(q, kk, v, block_q=64, block_k=64,
                              interpret=interpret)
    want = ref.flash_attention(q, kk, v)
    err = float(jnp.abs(got - want).max())
    us = time_call(ops.flash_attention, q, kk, v, block_q=64, block_k=64,
                   interpret=interpret,
                   sync=jax.block_until_ready)
    emit("kernel_flash_attention", us,
         f"maxerr={err:.2e} interp={interpret}")

    E, p = 16, 2048
    args = [jax.random.normal(k, (E, p)) for k in jax.random.split(key, 8)]
    got = ops.admm_edge_update(*args, rho=1.5, interpret=True)
    want = ref.admm_edge_update(*args, rho=1.5)
    err = max(float(jnp.abs(g - w).max()) for g, w in zip(got, want))
    us = time_call(ops.admm_edge_update, *args, rho=1.5, interpret=True,
                   sync=jax.block_until_ready)
    emit("kernel_admm_update", us, f"maxerr={err:.2e} interp=True")


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    main(fast=False)
