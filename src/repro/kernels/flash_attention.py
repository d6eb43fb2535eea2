"""Pallas TPU kernel: blocked causal attention with optional sliding window.

Grid: (batch*heads, num_q_blocks, num_kv_blocks). The last grid dim is the
sequential (arbitrary-marched) TPU dimension; online-softmax statistics (m, l)
and the output accumulator persist in VMEM scratch across kv steps and are
finalized on the last one. Causal + window structure skips fully-masked kv
blocks via @pl.when (no MXU work issued for them).

BlockSpec tiling (VMEM working set per grid step, bf16):
  q: (bQ, hd) + k,v: (bK, hd) + acc: (bQ, hd) f32 + p: (bQ, bK) f32
  with bQ=bK=256, hd=128: ~0.6 MB << 16 MB VMEM; MXU dims are multiples
  of 128 (bQ, bK, hd).  The VMEM use does not grow with S: the v5e
  compiler (tests/test_chip_compile.py) accepts S=1024 and S=4096 with
  16 heads of hd=128.

The GQA head expansion happens in ops.py (kv heads repeated to q heads)
so the kernel sees equal head counts.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
            scale: float, block_q: int, block_k: int, n_kv_blocks: int,
            window: Optional[int], seq_len: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q_start = qi * block_q
    k_start = ki * block_k
    # block-level reachability: any (q, k) pair in range?
    causal_live = k_start <= q_start + block_q - 1
    window_live = True
    if window is not None:
        # newest q in block attends back `window`; block dead if entirely older
        window_live = k_start + block_k - 1 > q_start - window

    @pl.when(causal_live & window_live)
    def _compute():
        q = q_ref[0].astype(jnp.float32)                     # (bQ, hd)
        k = k_ref[0].astype(jnp.float32)                     # (bK, hd)
        v = v_ref[0].astype(jnp.float32)
        logits = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale      # (bQ, bK)
        qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
        mask = kpos <= qpos
        if window is not None:
            mask &= kpos > qpos - window
        logits = jnp.where(mask, logits, NEG_INF)
        m_prev = m_ref[...]                                  # (bQ, 1)
        m_new = jnp.maximum(m_prev, logits.max(axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(logits - m_new)
        l_ref[...] = l_ref[...] * alpha + p.sum(axis=-1, keepdims=True)
        m_ref[...] = m_new
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(ki == n_kv_blocks - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-20)
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("window", "block_q", "block_k",
                                             "interpret"))
def flash_attention(q, k, v, *, window: Optional[int] = None,
                    block_q: int = 256, block_k: int = 256,
                    interpret: bool = False):
    """q, k, v: (B, S, H, hd) equal head counts -> (B, S, H, hd), causal.

    ``interpret`` is an explicit opt-in (CPU validation only); the default
    compiles for TPU — use ``kernels.dispatch`` for automatic selection.
    """
    B, S, H, hd = q.shape
    assert k.shape == v.shape == (B, S, H, hd)
    block_q = min(block_q, S)
    block_k = min(block_k, S)
    assert S % block_q == 0 and S % block_k == 0, (S, block_q, block_k)
    scale = hd ** -0.5
    # fold (B, H) into one grid axis; layout (BH, S, hd)
    qf = q.transpose(0, 2, 1, 3).reshape(B * H, S, hd)
    kf = k.transpose(0, 2, 1, 3).reshape(B * H, S, hd)
    vf = v.transpose(0, 2, 1, 3).reshape(B * H, S, hd)
    n_q, n_k = S // block_q, S // block_k
    grid = (B * H, n_q, n_k)
    kernel = functools.partial(
        _kernel, scale=scale, block_q=block_q, block_k=block_k,
        n_kv_blocks=n_k, window=window, seq_len=S)
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, hd), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, hd), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, hd), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, hd), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B * H, S, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, hd), jnp.float32),   # output accumulator
            pltpu.VMEM((block_q, 1), jnp.float32),    # running max m
            pltpu.VMEM((block_q, 1), jnp.float32),    # running denom l
        ],
        interpret=interpret,
    )(qf, kf, vf)
    return out.reshape(B, H, S, hd).transpose(0, 2, 1, 3)
