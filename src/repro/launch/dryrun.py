import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run (deliverable e).

For every (architecture x input shape x mesh): build the REAL train/serve
step (personalized params, coupling collective, AdamW), lower it with
ShapeDtypeStruct inputs (no allocation), compile it for the production mesh,
and record memory_analysis + cost_analysis + collective stats for §Dry-run /
§Roofline.

The XLA_FLAGS line above MUST precede any jax import — jax locks the device
count on first init. Run each combo in its own process:

    PYTHONPATH=src python -m repro.launch.dryrun --arch llama3-8b \
        --shape train_4k [--multi-pod] [--schedule gossip] --out results.json
"""

import argparse
import json
import time
import traceback

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.configs import ALIASES, get_config
from repro.coupling import CouplingConfig, make_state
from repro.core import random_geometric_graph
from repro.launch.mesh import make_production_mesh, n_agents_of
from repro.launch.shapes import SHAPES, InputShape, plan_decode
from repro.launch.sharding import (agent_axes_of, stacked_param_specs,
                                   batch_specs, stacked_cache_specs, named)
from repro.launch import hlo_analysis as ha
from repro.models import Model
from repro.models.common import batch_axes
from repro.optim import AdamWConfig
from repro.train import TrainConfig, make_train_step
from repro.train.trainer import TrainState, init_train_state


def active_param_count(cfg, model: Model) -> int:
    total = model.param_count()
    if not cfg.n_experts:
        return total
    expert_extra = 3 * cfg.d_model * cfg.d_ff * (cfg.n_experts - cfg.top_k)
    return total - expert_extra * cfg.n_layers


def abstract_like(tree):
    return jax.tree_util.tree_map(
        lambda l: jax.ShapeDtypeStruct(l.shape, l.dtype), tree)


def build_train(cfg, shape: InputShape, mesh, schedule: str, coupling: str,
                every: int = 1, mix_dtype=jnp.float32):
    model = Model(cfg)
    A = n_agents_of(mesh)
    tcfg = TrainConfig(
        n_agents=A, steps=10_000, optimizer=AdamWConfig(),
        coupling=CouplingConfig(mode=coupling, schedule=schedule,
                                every=every, mix_dtype=mix_dtype))
    graph = random_geometric_graph(A, k=3, seed=0)
    cstate = make_state(graph, np.linspace(0.3, 1.0, A), tcfg.coupling.alpha)
    pspecs = stacked_param_specs(model, mesh)
    step = make_train_step(model, tcfg, cstate, mesh=mesh, spmd=True,
                           param_specs=pspecs)

    state_abs = jax.eval_shape(
        lambda: init_train_state(model, tcfg, jax.random.PRNGKey(0)))
    batch_abs = model.input_specs(shape.global_batch, shape.seq_len, "train")

    state_specs = TrainState(
        params=pspecs, solitary=pspecs,
        opt_state={"m": pspecs, "v": pspecs, "count": P()},
        step=P())
    bspecs = batch_specs(model, mesh, "train")
    agent = agent_axes_of(mesh)
    metric_specs = {"loss": P(), "loss_per_agent": P(agent), "grad_norm": P(),
                    "ce": P(), "aux": P()}
    jitted = jax.jit(step,
                     in_shardings=(named(state_specs, mesh),
                                   named(bspecs, mesh)),
                     out_shardings=(named(state_specs, mesh),
                                    named(metric_specs, mesh)))
    return jitted, (state_abs, batch_abs), model


def build_prefill(cfg, shape: InputShape, mesh):
    model = Model(cfg)
    A = n_agents_of(mesh)
    b = shape.global_batch // A
    assert b >= 1, (shape.name, A)
    plan = plan_decode(cfg, InputShape(shape.name, shape.seq_len,
                                       shape.global_batch, "decode"))
    agent = agent_axes_of(mesh)

    def prefill_step(params, batch):
        with batch_axes(()):
            return jax.vmap(
                lambda p, bb: model.prefill(p, bb, cache_len=plan.cache_len),
                spmd_axis_name=agent)(params, batch)

    pspecs = stacked_param_specs(model, mesh)
    base_b = model.input_specs(b, shape.seq_len, "train")
    batch_abs = jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct((A,) + s.shape, s.dtype), base_b)
    bspecs = jax.tree_util.tree_map(
        lambda s: P(agent, *([None] * len(s.shape))), base_b)
    params_abs = jax.eval_shape(
        lambda: jax.tree_util.tree_map(
            lambda l: jnp.broadcast_to(l[None], (A,) + l.shape),
            model.init(jax.random.PRNGKey(0))))
    jitted = jax.jit(prefill_step,
                     in_shardings=(named(pspecs, mesh), named(bspecs, mesh)))
    return jitted, (params_abs, batch_abs), model


def build_decode(cfg, shape: InputShape, mesh, lockstep: bool = False):
    """Personalized decode: each agent serves its own model on its batch
    slice (global_batch = A * b). When global_batch < n_agents (long_500k:
    one 524k-token stream), serving degenerates to a single shared model
    with pure tensor parallelism — the agent axes are idle, which is the
    honest picture for batch-1 decode and is called out in §Dry-run."""
    model = Model(cfg)
    A = n_agents_of(mesh)
    plan = plan_decode(cfg, shape)
    agent = agent_axes_of(mesh)
    personalized = shape.global_batch >= A

    if personalized:
        b = shape.global_batch // A

        def serve_step(params, cache, batch):
            with batch_axes(()):
                return jax.vmap(
                    lambda p, c, bb: model.decode_step(
                        p, c, bb, window=plan.window, ring=plan.ring,
                        lockstep=lockstep),
                    spmd_axis_name=agent)(params, cache, batch)

        pspecs = stacked_param_specs(model, mesh)
        cspecs = stacked_cache_specs(model, mesh)
        params_abs = jax.eval_shape(
            lambda: jax.tree_util.tree_map(
                lambda l: jnp.broadcast_to(l[None], (A,) + l.shape),
                model.init(jax.random.PRNGKey(0))))
        cache_abs = jax.eval_shape(
            lambda: jax.tree_util.tree_map(
                lambda l: jnp.broadcast_to(l[None], (A,) + l.shape),
                model.init_cache(b, plan.cache_len)))
        tok_shape = (A, b, cfg.n_codebooks) if cfg.family == "audio" \
            else (A, b)
        batch_abs = {"token": jax.ShapeDtypeStruct(tok_shape, jnp.int32)}
        bspecs = {"token": P(agent, *([None] * (len(tok_shape) - 1)))}
    else:
        b = shape.global_batch

        def serve_step(params, cache, batch):
            with batch_axes(()):
                return model.decode_step(params, cache, batch,
                                         window=plan.window, ring=plan.ring,
                                         lockstep=lockstep)

        from repro.launch.sharding import resolve, _map_specs
        pspecs = _map_specs(lambda s: resolve(s, mesh), model.param_pspecs())
        base_c = model.cache_pspecs()
        cspecs = {"layers": _map_specs(
            lambda s: resolve(s, mesh, batch_to=()), base_c["layers"]),
            "pos": P(None)}
        params_abs = jax.eval_shape(
            lambda: model.init(jax.random.PRNGKey(0)))
        cache_abs = jax.eval_shape(
            lambda: model.init_cache(b, plan.cache_len))
        tok_shape = (b, cfg.n_codebooks) if cfg.family == "audio" else (b,)
        batch_abs = {"token": jax.ShapeDtypeStruct(tok_shape, jnp.int32)}
        bspecs = {"token": P(*([None] * len(tok_shape)))}

    jitted = jax.jit(serve_step,
                     in_shardings=(named(pspecs, mesh), named(cspecs, mesh),
                                   named(bspecs, mesh)))
    return jitted, (params_abs, cache_abs, batch_abs), model


def _variant_cfg(cfg, reps_list):
    """Reduced-depth, scan-free cfg for exact HLO cost accounting.

    XLA's cost_analysis counts a while/scan body ONCE regardless of trip
    count, so the real (scanned) program under-reports flops/bytes/collective
    traffic. We therefore measure depth-1 and depth-2 unrolled variants
    (ref attention + parallel mLSTM = no scans anywhere except sLSTM's
    inherent time recurrence, corrected analytically) and extrapolate
    linearly in depth — exact for homogeneous layer stacks.
    """
    import dataclasses as dc
    groups = cfg.scan_groups()
    pattern = []
    for (unit, _), r in zip(groups, reps_list):
        pattern += list(unit) * r
    return dc.replace(cfg, n_layers=len(pattern), pattern=tuple(pattern),
                      scan_layers=False, attn_impl="ref",
                      mlstm_impl="parallel")


_COST_KEYS = ("flops", "bytes")


def _measure(cfg_v, shape, mesh, mode, schedule, coupling, every=1,
             mix_dtype=jnp.float32, lockstep=False):
    if mode == "train":
        jitted, args, _ = build_train(cfg_v, shape, mesh, schedule, coupling,
                                      every=every, mix_dtype=mix_dtype)
    elif mode == "prefill":
        jitted, args, _ = build_prefill(cfg_v, shape, mesh)
    else:
        jitted, args, _ = build_decode(cfg_v, shape, mesh, lockstep=lockstep)
    with jax.set_mesh(mesh):
        compiled = jitted.lower(*args).compile()
    cost = compiled.cost_analysis()
    coll = ha.collective_stats(compiled.as_text())
    vec = {"flops": float(cost.get("flops", 0.0)),
           "bytes": float(cost.get("bytes accessed", 0.0))}
    for kind, st in coll.items():
        for f in ("count", "result_bytes", "wire_bytes"):
            vec[f"coll/{kind}/{f}"] = float(st[f])
    return vec


def _vec_op(a, b, f):
    return {k: f(a.get(k, 0.0), b.get(k, 0.0)) for k in set(a) | set(b)}


def _slstm_correction(cfg, shape, n_devices: int) -> dict:
    """Analytical flops/bytes for sLSTM time-scan bodies (counted once by
    XLA): recurrent gate matmuls 8*d*hd flops + ~40*d elementwise per step
    per sample, x3 for fwd+bwd-with-remat. Whole-program totals."""
    n_slstm = sum(1 for k in cfg.layer_kinds if k == "slstm")
    if not n_slstm or shape.mode == "decode":
        return {"flops": 0.0, "bytes": 0.0}
    d = cfg.d_model
    hd = d // cfg.n_heads
    steps = shape.seq_len * shape.global_batch      # token-steps
    fl = n_slstm * steps * (8.0 * d * hd + 40.0 * d)
    by = n_slstm * steps * (48.0 * d)
    mult = 3.0 if shape.mode == "train" else 1.0
    return {"flops": fl * mult, "bytes": by * mult}


def extrapolated_costs(cfg, shape, mesh, mode, schedule, coupling, every=1,
                       mix_dtype=jnp.float32, lockstep=False) -> dict:
    groups = cfg.scan_groups()
    G = len(groups)
    kw = dict(every=every, mix_dtype=mix_dtype, lockstep=lockstep)
    c0 = _measure(_variant_cfg(cfg, [1] * G), shape, mesh, mode, schedule,
                  coupling, **kw)
    total = dict(c0)
    for g, (unit, reps) in enumerate(groups):
        if reps == 1:
            continue
        reps_list = [2 if i == g else 1 for i in range(G)]
        cg = _measure(_variant_cfg(cfg, reps_list), shape, mesh, mode,
                      schedule, coupling, **kw)
        unit_cost = _vec_op(cg, c0, lambda a, b: a - b)
        total = _vec_op(total, unit_cost,
                        lambda a, b: a + (reps - 1) * b)
    corr = _slstm_correction(cfg, shape, int(np.prod(mesh.devices.shape)))
    nd = int(np.prod(mesh.devices.shape))
    total["flops"] += corr["flops"] / nd      # cost_analysis is per-device
    total["bytes"] += corr["bytes"] / nd
    return total


def run_one(arch: str, shape_name: str, multi_pod: bool, schedule: str,
            coupling: str, attn_impl: str, skip_variants: bool = False,
            every: int = 1, mix_dtype="f32", serve_dtype="f32",
            seq_shard: bool = True, lockstep: bool = False,
            moe_impl: str = "scatter", kv_shard: str = "seq",
            tag: str = "") -> dict:
    import dataclasses as dc
    cfg = get_config(arch, "full")
    overrides = {}
    if attn_impl:
        overrides["attn_impl"] = attn_impl
    if not seq_shard:
        overrides["seq_shard"] = False
    if moe_impl != "scatter":
        overrides["moe_impl"] = moe_impl
    if kv_shard != "seq":
        overrides["kv_shard"] = kv_shard
    shape = SHAPES[shape_name]
    if serve_dtype == "bf16" and shape.mode != "train":
        # serving weights in bf16 (training keeps f32 master weights)
        overrides["param_dtype"] = jnp.bfloat16
    if overrides:
        cfg = dc.replace(cfg, **overrides)
    mesh = make_production_mesh(multi_pod=multi_pod)
    rec = {"arch": cfg.name, "shape": shape_name, "mode": shape.mode,
           "multi_pod": multi_pod, "schedule": schedule, "coupling": coupling,
           "tag": tag,
           "mesh": "x".join(map(str, mesh.devices.shape)),
           "n_devices": int(np.prod(mesh.devices.shape))}
    mixd = jnp.bfloat16 if mix_dtype == "bf16" else jnp.float32
    t0 = time.time()
    if shape.mode == "train":
        jitted, args, model = build_train(cfg, shape, mesh, schedule,
                                          coupling, every=every,
                                          mix_dtype=mixd)
        tokens = shape.global_batch * shape.seq_len
        mf = ha.model_flops_train
    elif shape.mode == "prefill":
        jitted, args, model = build_prefill(cfg, shape, mesh)
        tokens = shape.global_batch * shape.seq_len
        mf = lambda n, t, a=0: ha.model_flops_decode(n, t, a)
    else:
        jitted, args, model = build_decode(cfg, shape, mesh,
                                           lockstep=lockstep)
        tokens = shape.global_batch
        mf = ha.model_flops_decode
    rec["param_count"] = model.param_count()
    rec["active_params"] = active_param_count(cfg, model)

    with jax.set_mesh(mesh):
        lowered = jitted.lower(*args)
        rec["lower_s"] = round(time.time() - t0, 1)
        t1 = time.time()
        compiled = lowered.compile()
        rec["compile_s"] = round(time.time() - t1, 1)

    mem = compiled.memory_analysis()
    print(mem)
    for attr in ("argument_size_in_bytes", "output_size_in_bytes",
                 "temp_size_in_bytes", "generated_code_size_in_bytes",
                 "alias_size_in_bytes"):
        v = getattr(mem, attr, None)
        if v is not None:
            rec[attr] = int(v)
    cost = compiled.cost_analysis()
    # raw (scanned) numbers — under-report loop bodies; kept for reference
    rec["scanned_flops"] = float(cost.get("flops", 0.0))
    rec["scanned_bytes"] = float(cost.get("bytes accessed", 0.0))
    rec["collectives_scanned"] = ha.collective_stats(compiled.as_text())

    if skip_variants:
        rec["ok"] = True
        return rec

    ex = extrapolated_costs(cfg, shape, mesh, shape.mode, schedule, coupling,
                            every=every, mix_dtype=mixd, lockstep=lockstep)
    rec["cost_flops"] = ex["flops"]              # per-device, scan-corrected
    rec["cost_bytes"] = ex["bytes"]
    coll = {}
    for k, v in ex.items():
        if k.startswith("coll/"):
            _, kind, field = k.split("/")
            # repro-lint: disable=RPL002  dict write, not an array scatter
            coll.setdefault(kind, {})[field] = v
    rec["collectives"] = coll
    A = n_agents_of(mesh)
    score_est = ha.score_traffic_estimate(cfg, shape, A)
    rec["cost_bytes_flash"] = max(ex["bytes"] - score_est, 0.0)
    roof = ha.roofline_terms({"flops": ex["flops"],
                              "bytes accessed": rec["cost_bytes_flash"]},
                             coll, rec["n_devices"])
    rec["roofline"] = roof.as_dict()
    n_active = rec["active_params"]
    rec["model_flops"] = mf(rec["param_count"], tokens, n_active)
    # cost_flops is per-device; model_flops is whole-program
    total_hlo_flops = rec["cost_flops"] * rec["n_devices"]
    rec["useful_flop_ratio"] = (rec["model_flops"] / total_hlo_flops
                                if total_hlo_flops else 0.0)
    rec["ok"] = True
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--schedule", default="dense",
                    choices=["dense", "gossip"])
    ap.add_argument("--coupling", default="mp",
                    choices=["none", "consensus", "mp", "cl"])
    ap.add_argument("--attn", default="", help="override attn_impl")
    ap.add_argument("--skip-variants", action="store_true",
                    help="compile-proof + memory only (no cost extrapolation)")
    # perf levers (§Perf)
    ap.add_argument("--every", type=int, default=1,
                    help="apply coupling every k steps (amortization noted "
                         "in the analysis; the collective still appears in "
                         "HLO once)")
    ap.add_argument("--mix-dtype", default="f32", choices=["f32", "bf16"],
                    help="wire dtype of the coupling collective")
    ap.add_argument("--serve-dtype", default="f32",
                    choices=["f32", "bf16"],
                    help="serving weight dtype (baseline f32)")
    ap.add_argument("--lockstep", action="store_true",
                    help="fleet decode at a shared position (DUS cache writes)")
    ap.add_argument("--moe-impl", default="scatter",
                    choices=["scatter", "gather"])
    ap.add_argument("--kv-shard", default="seq", choices=["seq", "heads"])
    ap.add_argument("--no-seq-shard", action="store_true")
    ap.add_argument("--tag", default="", help="record tag for perf runs")
    ap.add_argument("--out", default="")
    args = ap.parse_args()

    archs = sorted(set(ALIASES.values())) if args.arch == "all" \
        else [args.arch]
    shapes = list(SHAPES) if args.shape == "all" else [args.shape]
    records = []
    for arch in archs:
        for shape in shapes:
            tag = f"{arch} x {shape} ({'2pod' if args.multi_pod else '1pod'})"
            print(f"=== DRYRUN {tag} ===", flush=True)
            try:
                rec = run_one(arch, shape, args.multi_pod, args.schedule,
                              args.coupling, args.attn,
                              skip_variants=args.skip_variants,
                              every=args.every, mix_dtype=args.mix_dtype,
                              serve_dtype=args.serve_dtype,
                              seq_shard=not args.no_seq_shard,
                              lockstep=args.lockstep, moe_impl=args.moe_impl,
                              kv_shard=args.kv_shard, tag=args.tag)
            except Exception as e:
                traceback.print_exc()
                rec = {"arch": arch, "shape": shape,
                       "multi_pod": args.multi_pod, "ok": False,
                       "error": f"{type(e).__name__}: {e}"}
            records.append(rec)
            print(json.dumps({k: v for k, v in rec.items()
                              if k != "collectives"}, indent=1), flush=True)
            if args.out:
                existing = []
                if os.path.exists(args.out):
                    with open(args.out) as f:
                        existing = json.load(f)
                # replace same-key records
                keyf = lambda r: (r.get("arch"), r.get("shape"),
                                  r.get("multi_pod"), r.get("schedule"),
                                  r.get("coupling"), r.get("tag", ""))
                existing = [r for r in existing if keyf(r) != keyf(rec)]
                existing.append(rec)
                with open(args.out, "w") as f:
                    json.dump(existing, f, indent=1)
    bad = [r for r in records if not r.get("ok")]
    print(f"done: {len(records) - len(bad)} ok, {len(bad)} failed")
    return 1 if bad else 0


if __name__ == "__main__":
    raise SystemExit(main())
