"""Network-simulator scaling benchmark (DESIGN.md §4, §9, §14).

Runs the sparse event-driven MP-gossip engine across agent counts and fault
scenarios, recording throughput (rounds/s, events/s) and peak host memory.
The point of the exercise: at n = 10,000 (k = 8, p = 32) the dense
(n, n, p) knowledge state alone would be 12.8 GB (x5 for ADMM) and blows the
4 GB host budget — the sparse engine's whole footprint is tens of MB, so
10k-50k agents are routine.

    PYTHONPATH=src python benchmarks/bench_network_sim.py \
        --ns 1000,10000 --scenarios clean,lossy-10 --rounds 200

``--sharded`` additionally runs the graph-partitioned engine
(``simulate.partition``) on a mesh of ``--shards`` devices and reports the
event-throughput ratio over the single-device run.  Under
``JAX_PLATFORMS=cpu`` the devices are XLA fake host devices, which this
script force-creates (the flag must precede jax init, so it is set at
import time when --sharded is given); on an accelerator host the mesh is
the real devices, and ``--shards`` above their count is an error.
Each sharded run also records the per-round halo wire bytes under every
``HaloCodec`` and fails when int8 exceeds ``--halo-max-int8-ratio`` (0.35)
of f32.  ``--fused`` (mp only) reruns each config through the fused
``round_step`` dispatch op and reports its events/s speedup over the
per-op sequence (gated by ``--fused-min-ratio`` when given; the fused run
must also reproduce the default engine's exact event counters).

Besides the CSV rows (name,us,derived — same convention as the other
benchmarks), every invocation writes a machine-readable
``BENCH_network_sim.json`` (``--out``) with per-run events/s, RSS, core
count, sharded ratio, and — under ``--overhead`` — the telemetry-enabled
rerun and its events/s overhead percentage.  ``--baseline
BENCH_network_sim.baseline.json`` turns the run into a CI gate: it fails on
>2x per-run events/s regression after normalizing by the median slowdown
across all runs (so a uniformly slower runner doesn't trip it) and on any
drift in the deterministic delivered/dropped/invalid counters when the
invocation shape matches the baseline's.  Refresh the committed baseline
with the CI invocation plus ``--out BENCH_network_sim.baseline.json``.

``--run-dir DIR`` records each telemetry-enabled run as a run directory
(manifest.json + metrics.jsonl, rendered by ``tools/trace_report.py``);
``--profile DIR`` wraps one timed single-device run per (scenario, n) in
``jax.profiler.trace`` so the ``repro/<op>/<impl>`` named scopes from
``kernels.dispatch`` show up attributed in TensorBoard/Perfetto.
"""

from __future__ import annotations

import argparse
import json
import os
import resource
import sys
import time


def _requested_shards(argv) -> int:
    for i, a in enumerate(argv):
        if a == "--shards" and i + 1 < len(argv):
            return int(argv[i + 1])
        if a.startswith("--shards="):
            return int(a.split("=", 1)[1])
    return 8


# fake host devices only where the CPU is the requested platform: on a TPU
# host the mesh is the real chips, and asking for more shards than exist
# raises in make_sim_mesh instead of silently running fewer
if "--sharded" in sys.argv and os.environ.get("JAX_PLATFORMS") == "cpu" \
        and "host_platform_device_count" not in os.environ.get("XLA_FLAGS",
                                                               ""):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + f" --xla_force_host_platform_device_count="
          f"{_requested_shards(sys.argv)}").strip()

import jax  # noqa: E402
import numpy as np  # noqa: E402

sys.path.insert(0, __file__.rsplit("/", 1)[0])
from common import emit, time_call  # noqa: E402

from repro.core.losses import pad_datasets, solitary_mean  # noqa: E402
from repro.kernels.dispatch import ReproBackend  # noqa: E402
from repro.launch.sim_mesh import (HaloCodec,  # noqa: E402
                                   halo_payload_bytes)
from repro.simulate import (GraphPartition, get_scenario,  # noqa: E402
                            greedy_partition, random_geometric_topology,
                            run_cl_scenario, run_cl_scenario_sharded,
                            run_joint_scenario, run_joint_scenario_sharded,
                            run_mp_scenario, run_mp_scenario_sharded)
from repro.telemetry import (TelemetryConfig, build_manifest,  # noqa: E402
                             trace_rows, write_run)

#: graph-learning knobs for --algo joint (rate/temperature/cadence chosen so
#: the learned graph moves every few rounds without pruning the whole
#: candidate set; see DESIGN.md §13)
JOINT_KW = dict(eta_graph=0.3, lam=1.0, graph_every=5, prune_eps=1e-3)

#: events/s regression gate vs baseline, after machine-speed normalization
MAX_SLOWDOWN = 2.0


def peak_rss_mb() -> float:
    return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1024.0


def _make_data(n: int, p: int, seed: int):
    """Per-agent quadratic-loss samples (3 draws around a random mean)."""
    rng = np.random.default_rng(seed + 1)
    x = rng.standard_normal((n, 3, p)).astype(np.float32)
    return pad_datasets(list(x), [np.zeros(3)] * n)


def _single_runner(algo: str, topo, p: int, seed: int):
    """(run(cond, rounds, batch, record_every) -> trace) for one device."""
    rng = np.random.default_rng(seed)
    if algo == "admm":
        data = _make_data(topo.n, p, seed)
        sol = np.asarray(solitary_mean(data), np.float32)
        return lambda cond, **kw: run_cl_scenario(topo, data, 0.1, 1.0,
                                                  cond, theta_sol=sol, **kw)
    theta_sol = rng.standard_normal((topo.n, p)).astype(np.float32)
    c = rng.uniform(0.05, 1.0, topo.n).astype(np.float32)
    if algo == "joint":
        return lambda cond, **kw: run_joint_scenario(
            topo, theta_sol, c, 0.9, cond, **JOINT_KW, **kw)
    return lambda cond, **kw: run_mp_scenario(topo, theta_sol, c, 0.9,
                                              cond, **kw)


def _sharded_runner(algo: str, topo, p: int, seed: int):
    rng = np.random.default_rng(seed)
    if algo == "admm":
        data = _make_data(topo.n, p, seed)
        sol = np.asarray(solitary_mean(data), np.float32)
        return lambda cond, **kw: run_cl_scenario_sharded(
            topo, data, 0.1, 1.0, cond, theta_sol=sol, **kw)
    theta_sol = rng.standard_normal((topo.n, p)).astype(np.float32)
    c = rng.uniform(0.05, 1.0, topo.n).astype(np.float32)
    if algo == "joint":
        return lambda cond, **kw: run_joint_scenario_sharded(
            topo, theta_sol, c, 0.9, cond, **JOINT_KW, **kw)
    return lambda cond, **kw: run_mp_scenario_sharded(topo, theta_sol, c,
                                                      0.9, cond, **kw)


def bench_one(n: int, k: int, p: int, scenario_name: str, rounds: int,
              batch: int, seed: int = 0, algo: str = "mp", repeats: int = 1,
              telemetry=None, profile_dir=None, backend=None):
    """Timed single-device run; returns (report row, trace).

    ``backend`` (mp only) routes the round body through a fused
    ``round_step`` dispatch impl instead of the per-op sequence.
    """
    scenario = get_scenario(scenario_name)
    t0 = time.perf_counter()
    topo = random_geometric_topology(n, k=k, seed=seed)
    build_s = time.perf_counter() - t0

    cond = scenario.make_conditions(rounds)
    run = _single_runner(algo, topo, p, seed)

    # warmup with IDENTICAL static args + shapes: the engine's runner is a
    # module-level jit, so this compiles the exact program the timed run
    # reuses (steady-state events/s, no trace/compile in the measurement)
    record_every = max(1, rounds // 10)
    kw = dict(rounds=rounds, batch=batch, seed=seed,
              record_every=record_every, telemetry=telemetry)
    if backend is not None:
        kw["backend"] = backend
    tr = run(cond, **kw)
    if profile_dir is not None:
        with jax.profiler.trace(profile_dir):
            run(cond, **kw)
    dt = time_call(run, cond, repeats=repeats, warmup=0, **kw) / 1e6

    # the ADMM state carries 5 extra (n, k, p) arrays beyond MP's one; the
    # joint engine adds the learned (n, k) weight + liveness tables
    state_mb = topo.state_bytes(p) / 2**20
    if algo == "admm":
        state_mb += 4 * 4 * n * topo.k_max * p / 2**20
    elif algo == "joint":
        state_mb += 5 * n * topo.k_max / 2**20
    row = {
        "n": n, "k_max": topo.k_max, "p": p, "scenario": scenario_name,
        "rounds": tr.rounds, "batch": batch, "events": tr.events,
        "time_s": dt, "build_s": build_s,
        "rounds_per_s": tr.rounds / dt, "events_per_s": tr.events / dt,
        "delivered": tr.delivered, "dropped": tr.dropped,
        "invalid": tr.invalid,
        "sparse_state_mb": state_mb,
        "dense_state_mb": topo.dense_state_bytes(p) / 2**20
        * (5 if algo == "admm" else 1),
        "peak_rss_mb": peak_rss_mb(),
    }
    return row, tr


def bench_one_sharded(n: int, k: int, p: int, scenario_name: str,
                      rounds: int, batch: int, shards: int,
                      seed: int = 0, algo: str = "mp",
                      repeats: int = 1) -> dict:
    """Timed sharded run (partition + event-stream build reported apart)."""
    scenario = get_scenario(scenario_name)
    topo = random_geometric_topology(n, k=k, seed=seed)
    cond = scenario.make_conditions(rounds)
    record_every = max(1, rounds // 10)
    run = _sharded_runner(algo, topo, p, seed)

    t0 = time.perf_counter()
    assignment = greedy_partition(topo, shards)
    part_s = time.perf_counter() - t0

    kw = dict(rounds=rounds, batch=batch, seed=seed,
              record_every=record_every, n_shards=shards,
              assignment=assignment)
    tr = run(cond, **kw)                                        # warmup
    dt = time_call(run, cond, repeats=repeats, warmup=0, **kw) / 1e6
    # per-round halo wire bytes under each codec (what the telemetry
    # halo_bytes column would account; the CL payload stacks 1 + 3k rows)
    part = GraphPartition.build(topo, assignment, tr.n_shards)
    row_shape = (1 + 3 * topo.k_max, p) if algo == "admm" else (p,)
    halo_bytes = {
        name: halo_payload_bytes(part.n_shards, part.boundary_size,
                                 HaloCodec(name).row_nbytes(row_shape),
                                 part.halo_size)
        for name in HaloCodec.NAMES}
    return {
        "time_s": dt, "part_s": part_s, "events": tr.events,
        "events_per_s": tr.events / dt, "n_shards": tr.n_shards,
        "edge_cut": tr.edge_cut, "halo": tr.halo_size,
        "local_batch": tr.local_batch, "overflow": tr.overflow,
        "halo_bytes_per_round": halo_bytes,
        "peak_rss_mb": peak_rss_mb(),
    }


def compare_to_baseline(report: dict, baseline: dict) -> list:
    """Gate failures of ``report`` vs a committed baseline (see module
    docstring for the rules).  Returns human-readable failure strings."""
    failures = []
    base_runs = {r["name"]: r for r in baseline.get("runs", [])}
    meta_keys = ("rounds", "k", "p", "algo", "batch")
    same_shape = all(report["meta"].get(m) == baseline.get("meta", {}).get(m)
                     for m in meta_keys)
    pairs = []               # (name, cur events/s, base events/s)
    for r in report["runs"]:
        b = base_runs.get(r["name"])
        if b is None:
            continue
        pairs.append((r["name"], r["events_per_s"], b["events_per_s"]))
        if "sharded" in r and "sharded" in b:
            pairs.append((r["name"] + "/sharded",
                          r["sharded"]["events_per_s"],
                          b["sharded"]["events_per_s"]))
        if "fused" in r and "fused" in b:
            pairs.append((r["name"] + "/fused",
                          r["fused"]["events_per_s"],
                          b["fused"]["events_per_s"]))
        if same_shape:
            for c in ("events", "delivered", "dropped", "invalid"):
                if c in b and r.get(c) != b[c]:
                    failures.append(
                        f"counter drift: {r['name']} {c} {r.get(c)} vs "
                        f"baseline {b[c]} (same seed+shape must be exact)")
    if pairs:
        # slowdown = base/cur; median across runs = runner speed, so only
        # runs that regressed relative to the rest of the suite trip the gate
        slowdowns = sorted(b / max(c, 1e-9) for _, c, b in pairs)
        machine = slowdowns[len(slowdowns) // 2]
        for name, cur, base in pairs:
            rel = (base / max(cur, 1e-9)) / max(machine, 1e-9)
            if rel > MAX_SLOWDOWN:
                failures.append(
                    f"throughput regression: {name} {cur:.0f} events/s vs "
                    f"baseline {base:.0f} ({rel:.2f}x the suite median "
                    f"drift)")
    return failures


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--ns", default="1000,10000")
    ap.add_argument("--k", type=int, default=8)
    ap.add_argument("--p", type=int, default=32)
    ap.add_argument("--rounds", type=int, default=200)
    ap.add_argument("--batch", type=int, default=0,
                    help="wake-ups per round (default n // 10)")
    ap.add_argument("--scenarios", default="clean,lossy-10")
    ap.add_argument("--algo", default="mp", choices=("mp", "admm", "joint"),
                    help="engine: MP gossip (run_mp_scenario), CL-ADMM "
                         "(run_cl_scenario), or joint model+graph learning "
                         "(run_joint_scenario)")
    ap.add_argument("--fused", action="store_true",
                    help="(mp only) also run the engine through the fused "
                         "round_step op and report the events/s speedup "
                         "over the per-op sequence")
    ap.add_argument("--fused-min-ratio", type=float, default=None,
                    help="fail if any fused run's speedup over the per-op "
                         "sequence falls below this ratio")
    ap.add_argument("--halo-max-int8-ratio", type=float, default=0.35,
                    help="with --sharded: fail if the int8 halo codec's "
                         "per-round wire bytes exceed this fraction of "
                         "f32's (0 disables the check)")
    ap.add_argument("--sharded", action="store_true",
                    help="also run the partitioned engine and report the "
                         "event-throughput ratio over one device")
    ap.add_argument("--shards", type=int, default=8,
                    help="mesh size for --sharded (forced as fake host "
                         "devices under JAX_PLATFORMS=cpu)")
    ap.add_argument("--repeats", type=int, default=1,
                    help="timed repeats per run (min is reported)")
    ap.add_argument("--overhead", action="store_true",
                    help="rerun each config with telemetry enabled and "
                         "report the events/s overhead percentage")
    ap.add_argument("--out", default="BENCH_network_sim.json")
    ap.add_argument("--baseline", default=None,
                    help="committed baseline JSON to gate against (fail on "
                         ">2x normalized events/s regression or counter "
                         "drift)")
    ap.add_argument("--run-dir", default=None,
                    help="record telemetry-enabled runs as run directories "
                         "(manifest.json + metrics.jsonl) under this path")
    ap.add_argument("--profile", default=None,
                    help="wrap one timed run per config in "
                         "jax.profiler.trace writing to this directory")
    args = ap.parse_args(argv)

    ns = [int(x) for x in args.ns.split(",") if x]
    names = [s for s in args.scenarios.split(",") if s]
    if args.fused and args.algo != "mp":
        print("# --fused applies to --algo mp only; ignoring", flush=True)
        args.fused = False
    print("name,us,derived", flush=True)
    runs = []
    failures = []
    worst_rss = 0.0
    worst_ratio = None
    worst_fused = None
    worst_overhead = None
    used_shards = 0
    for n in ns:
        batch = args.batch or max(1, n // 10)
        for name in names:
            r, _ = bench_one(n, args.k, args.p, name, args.rounds, batch,
                             algo=args.algo, repeats=args.repeats,
                             profile_dir=args.profile)
            r["name"] = f"network_sim/{args.algo}/{name}/n{n}"
            worst_rss = max(worst_rss, r["peak_rss_mb"])
            emit(r["name"], r["time_s"] * 1e6,
                 f"events/s={r['events_per_s']:.0f} "
                 f"rounds/s={r['rounds_per_s']:.1f} "
                 f"delivered={r['delivered']} dropped={r['dropped']} "
                 f"sparse_state_mb={r['sparse_state_mb']:.1f} "
                 f"dense_state_would_be_mb={r['dense_state_mb']:.0f} "
                 f"peak_rss_mb={r['peak_rss_mb']:.0f}")
            if args.overhead or args.run_dir:
                tr_row, tr = bench_one(n, args.k, args.p, name, args.rounds,
                                       batch, algo=args.algo,
                                       repeats=args.repeats,
                                       telemetry=TelemetryConfig(
                                           enabled=True))
                if args.overhead:
                    pct = 100.0 * (1.0 - tr_row["events_per_s"]
                                   / max(r["events_per_s"], 1e-9))
                    r["telemetry"] = {
                        "events_per_s": tr_row["events_per_s"],
                        "overhead_pct": pct,
                    }
                    worst_overhead = pct if worst_overhead is None \
                        else max(worst_overhead, pct)
                    emit(r["name"] + "/telemetry", tr_row["time_s"] * 1e6,
                         f"events/s={tr_row['events_per_s']:.0f} "
                         f"overhead_pct={pct:.1f}")
                if args.run_dir:
                    d = os.path.join(args.run_dir,
                                     f"{args.algo}-{name}-n{n}")
                    manifest = build_manifest(seed=0, extra={
                        "scenario": name, "n": n, "algo": args.algo,
                        "rounds": args.rounds, "batch": batch})
                    write_run(d, manifest, trace_rows(tr))
                    print(f"# wrote run dir {d}", flush=True)
            if args.fused:
                f_row, _ = bench_one(
                    n, args.k, args.p, name, args.rounds, batch,
                    algo=args.algo, repeats=args.repeats,
                    backend=ReproBackend.using(round_step="xla"))
                speedup = f_row["events_per_s"] / r["events_per_s"]
                for cnt in ("delivered", "dropped", "invalid"):
                    if f_row[cnt] != r[cnt]:
                        failures.append(
                            f"fused counter drift: {r['name']} {cnt} "
                            f"{f_row[cnt]} vs {r[cnt]} (the fused round "
                            f"must replay the identical scenario)")
                r["fused"] = {
                    "impl": "xla", "time_s": f_row["time_s"],
                    "events_per_s": f_row["events_per_s"],
                    "speedup_vs_default": speedup,
                }
                worst_fused = speedup if worst_fused is None \
                    else min(worst_fused, speedup)
                emit(r["name"] + "/fused", f_row["time_s"] * 1e6,
                     f"events/s={f_row['events_per_s']:.0f} "
                     f"speedup_vs_default={speedup:.2f}x")
            if args.sharded:
                s = bench_one_sharded(n, args.k, args.p, name, args.rounds,
                                      batch, args.shards, algo=args.algo,
                                      repeats=args.repeats)
                ratio = s["events_per_s"] / r["events_per_s"]
                s["ratio_vs_1dev"] = ratio
                r["sharded"] = s
                hb = s["halo_bytes_per_round"]
                if args.halo_max_int8_ratio and hb["f32"] > 0 \
                        and hb["int8"] > args.halo_max_int8_ratio * hb["f32"]:
                    failures.append(
                        f"halo codec regression: {r['name']} int8 wire "
                        f"bytes {hb['int8']} > "
                        f"{args.halo_max_int8_ratio:.2f}x f32 {hb['f32']}")
                worst_ratio = ratio if worst_ratio is None \
                    else min(worst_ratio, ratio)
                worst_rss = max(worst_rss, s["peak_rss_mb"])
                used_shards = s["n_shards"]
                emit(f"{r['name']}/sharded{s['n_shards']}",
                     s["time_s"] * 1e6,
                     f"events/s={s['events_per_s']:.0f} "
                     f"speedup_vs_1dev={ratio:.2f}x "
                     f"edge_cut={s['edge_cut']} halo={s['halo']} "
                     f"local_batch={s['local_batch']} "
                     f"overflow={s['overflow']} "
                     f"partition_s={s['part_s']:.2f} "
                     f"peak_rss_mb={s['peak_rss_mb']:.0f}")
            runs.append(r)
    budget_mb = 4096.0
    status = "OK" if worst_rss < budget_mb else "OVER"
    print(f"# peak_rss {worst_rss:.0f} MB vs budget {budget_mb:.0f} MB "
          f"-> {status}", flush=True)
    if worst_ratio is not None:
        print(f"# sharded speedup (min over runs) {worst_ratio:.2f}x on "
              f"{used_shards} devices ({os.cpu_count()} host cores)",
              flush=True)
    if worst_fused is not None:
        print(f"# fused round_step speedup (min over runs) "
              f"{worst_fused:.2f}x over the per-op sequence", flush=True)
        if args.fused_min_ratio and worst_fused < args.fused_min_ratio:
            failures.append(
                f"fused round_step speedup {worst_fused:.2f}x below the "
                f"--fused-min-ratio {args.fused_min_ratio:.2f}x target")
    if worst_overhead is not None:
        print(f"# telemetry overhead (max over runs) {worst_overhead:.1f}% "
              f"events/s", flush=True)

    report = {
        "meta": {
            "platform": jax.default_backend(),
            "jax": jax.__version__,
            "cores": os.cpu_count(),
            "algo": args.algo, "k": args.k, "p": args.p,
            "rounds": args.rounds, "batch": args.batch,
            "repeats": args.repeats,
            "ns": ns, "scenarios": names,
            "sharded": bool(args.sharded), "shards": used_shards or None,
            "fused": bool(args.fused),
            # cores is os.cpu_count() of THIS host: on CPU runners the
            # fake host devices time-share those cores, so ratio_vs_1dev
            # measures partition/collective overhead (not parallel
            # speedup) whenever shards > cores — compare ratios only
            # across runs with matching cores/shards
            "ratio_vs_1dev_caveat": (
                f"{used_shards or args.shards} shards on "
                f"{os.cpu_count()} host core(s); ratio_vs_1dev is not a "
                f"parallel-speedup claim when shards > cores"
            ) if args.sharded else None,
        },
        "runs": runs,
        "summary": {
            "peak_rss_mb": worst_rss,
            "rss_budget_mb": budget_mb,
            "rss_ok": worst_rss < budget_mb,
            "min_sharded_ratio": worst_ratio,
            "min_fused_speedup": worst_fused,
            "telemetry_overhead_pct": worst_overhead,
        },
    }
    with open(args.out, "w") as f:
        json.dump(report, f, indent=2)
    print(f"wrote {args.out}", flush=True)

    if args.baseline:
        with open(args.baseline) as f:
            baseline = json.load(f)
        failures += compare_to_baseline(report, baseline)
    for fail in failures:
        print(f"BASELINE FAILURE: {fail}", flush=True)
    if failures:
        return 1
    if args.baseline:
        print(f"baseline gate OK vs {args.baseline}", flush=True)
    return 0 if worst_rss < budget_mb else 1


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    raise SystemExit(main())
