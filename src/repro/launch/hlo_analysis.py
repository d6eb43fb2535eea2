"""HLO post-compile analysis: collective traffic + roofline terms.

``cost_analysis()`` gives FLOPs and HBM bytes but NOT collective bytes
(collectives are zero-flop ops to XLA), so we parse the compiled module text
and sum the sizes of every collective's result buffers. Wire-level bytes per
device are estimated with standard ring-algorithm factors.

Hardware constants (TPU v5e, per DESIGN.md §7): 197 TFLOP/s bf16 per chip,
819 GB/s HBM, ~50 GB/s/link ICI.
"""

from __future__ import annotations

import dataclasses
import re
from typing import Dict

PEAK_FLOPS = 197e12          # bf16 / chip
HBM_BW = 819e9               # bytes / s / chip
ICI_BW = 50e9                # bytes / s / link

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "f8e4m3fn": 1, "f8e5m2": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8, "c128": 16,
}

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")


# ring-algorithm wire factor per unit of *result* bytes
_WIRE_FACTOR = {
    "all-gather": 1.0,          # each device receives (n-1)/n of the result
    "all-reduce": 2.0,          # reduce-scatter + all-gather
    "reduce-scatter": 1.0,      # sends ~n-1 shards of result size... ~1x in
    "all-to-all": 1.0,
    "collective-permute": 1.0,
}

_ARRAY_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")
_OP_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?[\w.\-]+\s*=\s*(\([^=]*?\)|[a-z0-9]+\[[^\]]*\]\S*)\s+"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?\(", re.M)


def _type_bytes(type_str: str) -> int:
    total = 0
    for dtype, dims in _ARRAY_RE.findall(type_str):
        if dtype not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dtype]
    return total


def collective_stats(hlo_text: str) -> Dict[str, Dict[str, float]]:
    """Per-collective-kind result bytes, wire-model bytes, and op counts."""
    stats = {k: {"count": 0, "result_bytes": 0.0, "wire_bytes": 0.0}
             for k in _COLLECTIVES}
    for m in _OP_RE.finditer(hlo_text):
        type_str, kind = m.group(1), m.group(2)
        b = _type_bytes(type_str)
        stats[kind]["count"] += 1
        stats[kind]["result_bytes"] += b
        stats[kind]["wire_bytes"] += b * _WIRE_FACTOR[kind]
    return stats


@dataclasses.dataclass
class Roofline:
    flops: float                 # HLO flops (per full program, all devices)
    hbm_bytes: float
    collective_bytes: float      # wire-model bytes (per device, see note)
    n_devices: int
    compute_s: float
    memory_s: float
    collective_s: float

    @property
    def dominant(self) -> str:
        terms = {"compute": self.compute_s, "memory": self.memory_s,
                 "collective": self.collective_s}
        return max(terms, key=terms.get)

    def as_dict(self):
        return {**dataclasses.asdict(self), "dominant": self.dominant}


def roofline_terms(cost: Dict[str, float], coll: Dict[str, Dict[str, float]],
                   n_devices: int, links_per_chip: float = 2.0) -> Roofline:
    """Three roofline terms in seconds.

    cost_analysis of an SPMD executable is PER-DEVICE (the module is the
    per-device program); collective result bytes from the HLO text are also
    per-device buffer sizes.
    """
    flops = float(cost.get("flops", 0.0))
    hbm = float(cost.get("bytes accessed", 0.0))
    wire = sum(v["wire_bytes"] for v in coll.values())
    return Roofline(
        flops=flops, hbm_bytes=hbm, collective_bytes=wire,
        n_devices=n_devices,
        compute_s=flops / PEAK_FLOPS,
        memory_s=hbm / HBM_BW,
        collective_s=wire / (ICI_BW * links_per_chip),
    )


def score_traffic_estimate(cfg, shape, n_agents: int, tp: int = 16) -> float:
    """Per-device HBM bytes of materialized attention/mLSTM score matrices.

    The cost-measurement variants use the PARALLEL forms (ref attention,
    parallel mLSTM) whose S^2 score tensors hit HBM; the target Pallas
    kernels (flash_attention, chunked mLSTM) keep them in VMEM. Subtracting
    this estimate yields ``cost_bytes_flash`` — the memory-roofline term for
    the target implementation. Estimate: one f32 score tensor is written +
    read ~3x in fwd; backward with remat re-creates it and reads it ~3x more
    (train only).
    """
    S = shape.seq_len
    B_dev = max(shape.global_batch // n_agents, 1)
    mult = {"train": 6.0, "prefill": 3.0, "decode": 0.0}[shape.mode]
    if mult == 0.0:
        return 0.0
    total = 0.0
    for kind in cfg.layer_kinds:
        if kind.startswith("attn"):
            w = cfg.local_window if kind == "attn_local" else cfg.window
            kdim = min(S, w) if w else S
            h_dev = max(cfg.n_heads // tp, 1)
            total += B_dev * h_dev * S * kdim * 4.0 * mult
        elif kind == "mlstm":
            # logD + D + scores: ~3 (B,S,S,H) f32 tensors, heads unsharded
            total += B_dev * cfg.n_heads * S * S * 4.0 * mult * 2.0
    return total


def model_flops_train(n_params: int, n_tokens: int,
                      active_params: int = 0) -> float:
    """6 N D (dense) / 6 N_active D (MoE) — fwd+bwd per token."""
    n = active_params or n_params
    return 6.0 * n * n_tokens


def model_flops_decode(n_params: int, n_tokens: int,
                       active_params: int = 0) -> float:
    """2 N D for single-token decode (no backward)."""
    n = active_params or n_params
    return 2.0 * n * n_tokens
