"""Shared model machinery: config, parameter declaration, basic layers.

Parameters are declared ONCE via ``ParamDef`` (shape + PartitionSpec + init),
so ``init_params`` and ``param_pspecs`` can never drift apart (asserted by
tests/test_models_smoke.py::test_pspec_tree_matches_params).

Sharding conventions (DESIGN.md §4): the *base* model carries no agent dim —
PartitionSpecs here only reference the ``"model"`` tensor-parallel axis; the
coupling layer prepends the agent axis (("pod","data")) to every leaf.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


# ---------------------------------------------------------------------------
# Config
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                      # dense | moe | ssm | hybrid | vlm | audio
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: Optional[int] = None
    rope_theta: float = 10000.0
    # attention windowing: None = full causal. long-context decode shapes use
    # ``long_ctx_window`` on attention archs (DESIGN.md §5).
    window: Optional[int] = None
    long_ctx_window: int = 4096
    # MoE
    n_experts: int = 0
    top_k: int = 0
    capacity_factor: float = 1.25
    router_aux_weight: float = 0.01
    moe_seq_shard: bool = False      # expert-parallel all-to-all layout (perf lever)
    # dispatch realization: "scatter" writes tokens into the expert-sharded
    # buffer (SPMD turns that into a full-buffer reduce per layer);
    # "gather" scatters only int32 slot->token indices (tiny, replicated)
    # and gathers tokens locally — shard-local dispatch (§Perf A-series).
    moe_impl: str = "scatter"
    # hybrid (recurrentgemma / griffin)
    pattern: Tuple[str, ...] = ()    # per-layer mixer kinds; () -> all "attn"
    local_window: int = 2048
    conv_width: int = 4
    lru_dim: Optional[int] = None
    # ssm (xlstm)
    mlstm_proj_factor: float = 2.0
    slstm_ff: int = 0                # GeGLU hidden of sLSTM blocks (0 = 4d/3)
    mlstm_impl: str = "scan"         # scan (exact recurrent) | parallel (O(S^2))
    # vlm
    mrope_sections: Tuple[int, int, int] = (16, 24, 24)
    n_media_tokens: int = 0
    # audio
    n_codebooks: int = 1
    n_cond_tokens: int = 0
    # ffn
    ffn_kind: str = "swiglu"         # swiglu | geglu | gelu
    # numerics / implementation
    param_dtype: Any = jnp.float32
    compute_dtype: Any = jnp.bfloat16
    attn_impl: str = "chunked"       # ref | chunked | flash
    attn_chunk: int = 512
    remat: bool = True
    scan_layers: bool = True
    # Megatron-style sequence parallelism: the residual stream between blocks
    # is sharded over "model" along S, so remat-saved activations cost 1/TP.
    # GSPMD inserts the all-gather/reduce-scatter pair around each mixer/FFN.
    seq_shard: bool = True
    # KV-cache sharding over "model": "seq" = split-KV (S dim; GSPMD
    # replicates the cache around dynamic writes — §Perf C1), "heads" =
    # head_dim sharding (writes shard-local; attention combines partial
    # q.k dots with a logits-sized psum — §Perf C3).
    kv_shard: str = "seq"

    @property
    def hd(self) -> int:
        return self.head_dim if self.head_dim is not None \
            else self.d_model // self.n_heads

    @property
    def layer_kinds(self) -> Tuple[str, ...]:
        if self.pattern:
            assert len(self.pattern) == self.n_layers
            return self.pattern
        return ("attn",) * self.n_layers

    @property
    def r_dim(self) -> int:
        return self.lru_dim if self.lru_dim is not None else self.d_model

    @property
    def mlstm_inner(self) -> int:
        return int(self.mlstm_proj_factor * self.d_model)

    @property
    def slstm_hidden(self) -> int:
        if self.slstm_ff:
            return self.slstm_ff
        return int(math.ceil(self.d_model * 4 / 3 / 128) * 128)

    def scan_groups(self) -> Tuple[Tuple[Tuple[str, ...], int], ...]:
        """Decompose the layer stack into (unit, repetitions) scan groups.

        Finds the shortest repeating unit; a non-multiple tail becomes its own
        group (e.g. recurrentgemma 26L = (rec,rec,attn) x 8 + (rec,rec) x 1).
        """
        kinds = self.layer_kinds
        L = len(kinds)
        for ulen in range(1, L + 1):
            unit = kinds[:ulen]
            reps = L // ulen
            if kinds[:ulen * reps] == unit * reps:
                tail = kinds[ulen * reps:]
                groups = [(unit, reps)]
                if tail:
                    groups.append((tail, 1))
                return tuple(groups)
        return ((kinds, 1),)


# ---------------------------------------------------------------------------
# Parameter declaration
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class ParamDef:
    shape: Tuple[int, ...]
    spec: P                          # PartitionSpec over the base (agent-free) leaf
    init: str = "normal"             # normal | zeros | ones | lru_lambda
    scale: Optional[float] = None    # default: 1/sqrt(fan_in)


def _init_leaf(key, d: ParamDef, dtype):
    if d.init == "zeros":
        return jnp.zeros(d.shape, dtype)
    if d.init == "ones":
        return jnp.ones(d.shape, dtype)
    if d.init == "lru_lambda":
        # RG-LRU Lambda init: a = sigmoid(Lambda) uniform in [0.9, 0.999]
        u = jax.random.uniform(key, d.shape, jnp.float32, 0.9, 0.999)
        return jnp.log(u / (1.0 - u)).astype(dtype)
    scale = d.scale
    if scale is None:
        fan_in = d.shape[-2] if len(d.shape) >= 2 else d.shape[-1]
        scale = 1.0 / math.sqrt(max(fan_in, 1))
    return (scale * jax.random.normal(key, d.shape, jnp.float32)).astype(dtype)


def init_from_defs(defs, key, dtype) -> Dict:
    """Materialize a (nested) dict of ParamDef into parameters."""
    leaves, treedef = jax.tree_util.tree_flatten(
        defs, is_leaf=lambda x: isinstance(x, ParamDef))
    keys = jax.random.split(key, len(leaves))
    vals = [_init_leaf(k, d, dtype) for k, d in zip(keys, leaves)]
    return jax.tree_util.tree_unflatten(treedef, vals)


def pspecs_from_defs(defs) -> Dict:
    return jax.tree_util.tree_map(lambda d: d.spec, defs,
                                  is_leaf=lambda x: isinstance(x, ParamDef))


def abstract_from_defs(defs, dtype) -> Dict:
    return jax.tree_util.tree_map(
        lambda d: jax.ShapeDtypeStruct(d.shape, dtype), defs,
        is_leaf=lambda x: isinstance(x, ParamDef))


def stack_defs(defs: Dict, reps: int) -> Dict:
    """Prepend a scan (layer-repetition) dim to every ParamDef in a subtree."""
    def f(d: ParamDef):
        return ParamDef((reps,) + d.shape, P(None, *d.spec), d.init, d.scale)
    return jax.tree_util.tree_map(f, defs, is_leaf=lambda x: isinstance(x, ParamDef))


# ---------------------------------------------------------------------------
# Basic layers (pure functions; params are dict leaves)
# ---------------------------------------------------------------------------


def rms_norm(x, gamma, eps: float = 1e-6):
    dtype = x.dtype
    x32 = x.astype(jnp.float32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    return ((x32 * jax.lax.rsqrt(var + eps))
            * (1.0 + gamma.astype(jnp.float32))).astype(dtype)


def swiglu(x, w_gate, w_up, w_down):
    h = jax.nn.silu(x @ w_gate) * (x @ w_up)
    return h @ w_down


def gelu_glu(x, w_gate, w_up, w_down):
    h = jax.nn.gelu(x @ w_gate) * (x @ w_up)
    return h @ w_down


# --- rotary embeddings ------------------------------------------------------


def rope_freqs(hd: int, theta: float):
    return 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))


def apply_rope(x, positions, theta: float):
    """x: (..., S, H, hd); positions: broadcastable to (..., S)."""
    hd = x.shape[-1]
    freqs = rope_freqs(hd, theta)                      # (hd/2,)
    ang = positions[..., None].astype(jnp.float32) * freqs   # (..., S, hd/2)
    cos, sin = jnp.cos(ang)[..., None, :], jnp.sin(ang)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def apply_mrope(x, positions3, theta: float, sections: Tuple[int, int, int]):
    """Qwen2-VL multimodal RoPE.

    x: (B, S, H, hd); positions3: (3, B, S) — temporal/height/width position
    ids. head_dim/2 frequency slots are split into ``sections`` (summing to
    hd/2); each section takes its angle from the corresponding position id.
    Text tokens carry identical ids in all three planes => reduces to RoPE.
    """
    hd = x.shape[-1]
    assert sum(sections) == hd // 2, (sections, hd)
    freqs = rope_freqs(hd, theta)                      # (hd/2,)
    ang_all = positions3[..., None].astype(jnp.float32) * freqs  # (3, B, S, hd/2)
    sel = jnp.repeat(jnp.arange(3), jnp.asarray(sections),
                     total_repeat_length=hd // 2)      # (hd/2,) section owner
    ang = jnp.take_along_axis(
        jnp.moveaxis(ang_all, 0, -1), sel[None, None, :, None], axis=-1)[..., 0]
    cos, sin = jnp.cos(ang)[..., None, :], jnp.sin(ang)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# --- losses -----------------------------------------------------------------


def cross_entropy(logits, labels, mask=None):
    """Mean CE over valid positions. labels < 0 are ignored."""
    valid = (labels >= 0) if mask is None else mask & (labels >= 0)
    labels_safe = jnp.maximum(labels, 0)
    logz = jax.nn.logsumexp(logits.astype(jnp.float32), axis=-1)
    gold = jnp.take_along_axis(logits.astype(jnp.float32),
                               labels_safe[..., None], axis=-1)[..., 0]
    nll = logz - gold
    nll = jnp.where(valid, nll, 0.0)
    return jnp.sum(nll) / jnp.maximum(jnp.sum(valid), 1)


import contextlib
import contextvars

# Which mesh axes the *batch/agent* slot of activation constraints maps to.
# Direct (non-vmapped) execution: ("pod", "data"). Inside a per-agent vmap
# (spmd_axis_name carries the agent axes), the slot must resolve to None —
# the agent axes are already consumed by the vmapped dim.
_BATCH_AXES = contextvars.ContextVar("repro_batch_axes",
                                     default=("pod", "data"))
_AGENT_SLOT = ("pod", "data")


@contextlib.contextmanager
def batch_axes(names):
    token = _BATCH_AXES.set(tuple(names))
    try:
        yield
    finally:
        _BATCH_AXES.reset(token)


def _resolve_agent_slot(spec: P) -> P:
    cur = _BATCH_AXES.get()
    out = []
    for entry in spec:
        if isinstance(entry, tuple) and entry == _AGENT_SLOT:
            out.append(cur if cur else None)
        else:
            out.append(entry)
    return P(*out)


def adapt_pspec(spec: P, axis_names) -> P:
    """Drop references to mesh axes that don't exist in the ambient mesh.

    Specs in this package are written against the *multi-pod* axis set
    ("pod", "data", "model"); on a single-pod mesh the "pod" axis is absent
    and the spec degrades gracefully (("pod","data") -> "data").
    """
    out = []
    for entry in spec:
        if entry is None:
            out.append(None)
        elif isinstance(entry, (tuple, list)):
            kept = tuple(a for a in entry if a in axis_names)
            out.append(kept[0] if len(kept) == 1 else (kept or None))
        else:
            out.append(entry if entry in axis_names else None)
    return P(*out)


def adapt_pspec_tree(tree, mesh):
    names = tuple(mesh.axis_names)
    return jax.tree_util.tree_map(
        lambda s: adapt_pspec(s, names), tree,
        is_leaf=lambda s: isinstance(s, P))


def constrain(x, spec: P):
    """with_sharding_constraint adapted to the ambient mesh; no-op without one."""
    spec = _resolve_agent_slot(spec)
    try:
        mesh = jax.sharding.get_abstract_mesh()
        if mesh is None or mesh.empty:
            return x
        return jax.lax.with_sharding_constraint(
            x, adapt_pspec(spec, tuple(mesh.axis_names)))
    except (ValueError, RuntimeError):
        return x
