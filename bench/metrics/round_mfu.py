"""Scan, whole round: the round's share of the chip's roofline, in %.

The least time the chip could take for one round's algorithmic work (the
larger of FLOPs over peak FLOP/s and bytes over peak HBM bytes/s; the work
from ``bench/work/<algo>.py`` with the calls' own delivery counts, the
peaks from ``bench/peaks.json``) over the measured device time of a
round.  ``bound`` in the result says which of the two set it."""

import json
from pathlib import Path

PEAKS = Path(__file__).resolve().parents[1] / "peaks.json"


def peak(kind: str) -> dict:
    table = json.loads(PEAKS.read_text())["devices"]
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r}; "
                       f"bench/peaks.json has {sorted(table)}")
    return table[kind]


def read(ctx):
    t = ctx.scan_s_per_round()
    if not ctx.trace.scans or t <= 0 or not ctx.calls:
        return None
    cfg, tr = ctx.cell.config, ctx.cell.traffic
    delivered = sum(c["delivered"] for c in ctx.calls) / len(ctx.calls)
    flops, nbytes = ctx.work.per_round(cfg["n"], tr.batch, ctx.k_max,
                                       cfg["p"], delivered, tr.rounds,
                                       tr.record_every)
    pk = peak(ctx.device_kind)
    t_flops = flops / pk["flops_per_s"]
    t_bytes = nbytes / pk["hbm_bytes_per_s"]
    return dict(value=100.0 * max(t_flops, t_bytes) / t,
                bound="hbm" if t_bytes >= t_flops else "flops")
