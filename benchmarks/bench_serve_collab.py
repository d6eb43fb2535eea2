"""Gossip-backed personalization-service benchmark (DESIGN.md §16).

Runs MP gossip under faults with a Poisson-ish inference-request stream
interleaved, then times the serving plane in isolation: per record chunk
the benchmark *commits* the chunk's snapshot to the agent-state store,
*invalidates* the mixed-model cache at exactly the agents the chunk's
deliveries rewrote, and *serves* every request of the chunk by batched
decode.  The scan artifacts (theta history, replayed staleness counters,
dirty sets, request chunks) are precomputed once so the timed region is
pure serving — commit + invalidate + cache lookup + batched predict —
and requests/s measures the read path, not gossip.

    PYTHONPATH=src python benchmarks/bench_serve_collab.py \
        --ns 1000,10000 --rounds 200 --rate 50

Every run first proves the acceptance property in-bench: the gossip
trajectory with serving attached is bit-for-bit identical to the
serve-free run (reads never touch the scan).  Besides the CSV rows
(name,us,derived — same convention as the other benchmarks), every
invocation writes a machine-readable ``BENCH_serve_collab.json``
(``--out``) with per-run requests/s, cache hit rate, p50/p99 served
staleness, and the deterministic service counters.  ``--baseline
BENCH_serve_collab.baseline.json`` turns the run into a CI gate: it
fails on >2x per-run requests/s regression after normalizing by the
median slowdown across all runs (so a uniformly slower runner doesn't
trip it) and on any drift in the deterministic counters (requests,
hits, misses, invalidations) when the invocation shape matches the
baseline's.  Refresh the committed baseline with the CI invocation plus
``--out BENCH_serve_collab.baseline.json``.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import resource
import sys

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, __file__.rsplit("/", 1)[0])
from common import emit, time_call  # noqa: E402

from repro.serve import AgentStateStore, CollabServeEngine  # noqa: E402
from repro.simulate import (NetworkConditions, ScenarioSpec,  # noqa: E402
                            precompute_event_stream,
                            precompute_serve_stream,
                            random_geometric_topology, run_scenario,
                            serve_chunk_requests)
from repro.core.sparse import record_chunks  # noqa: E402
from repro.telemetry.metrics import (stream_dirty_chunks,  # noqa: E402
                                     stream_staleness_chunks)

#: requests/s regression gate vs baseline, after machine-speed normalization
MAX_SLOWDOWN = 2.0

#: deterministic service counters that must match the baseline exactly
#: whenever the invocation shape does
COUNTERS = ("requests", "cache_hits", "cache_misses", "cache_invalidations")


def peak_rss_mb() -> float:
    return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1024.0


def bench_one(n: int, k: int, p: int, rounds: int, rate: float, batch: int,
              serve_batch: int, seed: int = 0, repeats: int = 1):
    """One timed serve pass; returns (report row, failure strings)."""
    failures = []
    topo = random_geometric_topology(n, k=k, seed=seed)
    rng = np.random.default_rng(seed)
    theta_sol = rng.standard_normal((n, p)).astype(np.float32)
    c = rng.uniform(0.05, 1.0, n).astype(np.float32)
    record_every = max(1, rounds // 10)
    spec = ScenarioSpec(
        algo="mp", topology=topo, theta_sol=theta_sol, c=c, alpha=0.9,
        conditions=NetworkConditions(drop_prob=0.15, churn_rate=0.005),
        rounds=rounds, batch=batch, seed=seed, record_every=record_every,
        serve=precompute_serve_stream(n, rounds, rate=rate, seed=seed),
        serve_batch=serve_batch)

    tr = run_scenario(spec)                    # gossip + serve (warms jits)
    bare = run_scenario(dataclasses.replace(spec, serve=None))
    if not np.array_equal(tr.theta_hist, bare.theta_hist):
        failures.append(
            f"serve perturbation: n={n} gossip trajectory differs with "
            f"serving attached (reads must never touch the scan)")

    # precompute the scan artifacts the service consumes, so the timed
    # region is commit + invalidate + lookup + batched predict only
    record_every, n_rec = record_chunks(rounds, record_every)
    stream = precompute_event_stream(
        topo.device_tables(), jnp.asarray(topo.partition_halves()),
        spec.conditions, batch, seed, n_rec * record_every)
    dirty = stream_dirty_chunks(stream, n, n_rec, record_every)
    staleness = stream_staleness_chunks(stream, n, n_rec, record_every)
    requests = serve_chunk_requests(spec.serve, n_rec, record_every)
    hist = np.asarray(tr.theta_hist)

    def serve_pass():
        store = AgentStateStore(n, p)
        eng = CollabServeEngine(store, n, p, batch_size=serve_batch)
        for ci in range(n_rec):
            eng.commit((ci + 1) * record_every, hist[ci], staleness[ci],
                       dirty[ci])
            users, _ = requests[ci]
            if users.size:
                eng.serve(users)
        return eng.report()

    rep = serve_pass()                                          # warmup
    dt = time_call(serve_pass, repeats=repeats, warmup=0) / 1e6
    summ = rep.summary()
    if summ["requests"] != tr.serve.requests \
            or summ["cache_hits"] != tr.serve.hits:
        failures.append(
            f"replay drift: n={n} timed serve pass counters "
            f"{summ['requests']}/{summ['cache_hits']} vs in-run "
            f"{tr.serve.requests}/{tr.serve.hits}")
    row = {
        "n": n, "k_max": topo.k_max, "p": p, "rounds": rounds,
        "rate": rate, "batch": batch, "serve_batch": serve_batch,
        "chunks": n_rec, "time_s": dt,
        "requests_per_s": summ["requests"] / max(dt, 1e-9),
        "cache_hit_rate": summ["cache_hit_rate"],
        "served_staleness_p50": summ["served_staleness_p50"],
        "served_staleness_p99": summ["served_staleness_p99"],
        "peak_rss_mb": peak_rss_mb(),
        **{c_: summ[c_] for c_ in COUNTERS},
    }
    return row, failures


def compare_to_baseline(report: dict, baseline: dict) -> list:
    """Gate failures of ``report`` vs a committed baseline (see module
    docstring for the rules).  Returns human-readable failure strings."""
    failures = []
    base_runs = {r["name"]: r for r in baseline.get("runs", [])}
    meta_keys = ("rounds", "k", "p", "rate", "batch", "serve_batch")
    same_shape = all(report["meta"].get(m) == baseline.get("meta", {}).get(m)
                     for m in meta_keys)
    pairs = []               # (name, cur requests/s, base requests/s)
    for r in report["runs"]:
        b = base_runs.get(r["name"])
        if b is None:
            continue
        pairs.append((r["name"], r["requests_per_s"], b["requests_per_s"]))
        if same_shape:
            for c in COUNTERS + ("served_staleness_p50",
                                 "served_staleness_p99"):
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
                    f"throughput regression: {name} {cur:.0f} requests/s "
                    f"vs baseline {base:.0f} ({rel:.2f}x the suite median "
                    f"drift)")
    return failures


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--ns", default="1000,10000")
    ap.add_argument("--k", type=int, default=8)
    ap.add_argument("--p", type=int, default=32)
    ap.add_argument("--rounds", type=int, default=200)
    ap.add_argument("--rate", type=float, default=50.0,
                    help="inference requests per gossip round")
    ap.add_argument("--batch", type=int, default=0,
                    help="gossip wake-ups per round (default n // 10)")
    ap.add_argument("--serve-batch", type=int, default=256,
                    help="decode batch size (users per predict dispatch)")
    ap.add_argument("--repeats", type=int, default=1,
                    help="timed repeats per run (min is reported)")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny problem (CI bench-gate lane)")
    ap.add_argument("--out", default="BENCH_serve_collab.json")
    ap.add_argument("--baseline", default=None,
                    help="committed baseline JSON to gate against (fail on "
                         ">2x normalized requests/s regression or counter "
                         "drift)")
    args = ap.parse_args(argv)
    if args.smoke:
        args.ns, args.rounds, args.rate = "500", 80, 20.0

    ns = [int(x) for x in args.ns.split(",") if x]
    print("name,us,derived", flush=True)
    runs = []
    failures = []
    worst_rss = 0.0
    for n in ns:
        batch = args.batch or max(1, n // 10)
        r, fails = bench_one(n, args.k, args.p, args.rounds, args.rate,
                             batch, args.serve_batch, repeats=args.repeats)
        failures += fails
        r["name"] = f"serve_collab/mp/n{n}"
        worst_rss = max(worst_rss, r["peak_rss_mb"])
        emit(r["name"], r["time_s"] * 1e6,
             f"requests/s={r['requests_per_s']:.0f} "
             f"hit_rate={r['cache_hit_rate']:.2f} "
             f"staleness_p50={r['served_staleness_p50']:.0f} "
             f"staleness_p99={r['served_staleness_p99']:.0f} "
             f"invalidations={r['cache_invalidations']} "
             f"peak_rss_mb={r['peak_rss_mb']:.0f}")
        runs.append(r)

    report = {
        "meta": {
            "platform": jax.default_backend(),
            "jax": jax.__version__,
            "cores": os.cpu_count(),
            "k": args.k, "p": args.p, "rounds": args.rounds,
            "rate": args.rate, "batch": args.batch,
            "serve_batch": args.serve_batch, "repeats": args.repeats,
            "ns": ns, "smoke": bool(args.smoke),
        },
        "runs": runs,
        "summary": {
            "peak_rss_mb": worst_rss,
            "max_requests_per_s": max(r["requests_per_s"] for r in runs),
            "min_cache_hit_rate": min(r["cache_hit_rate"] for r in runs),
            "worst_staleness_p99": max(r["served_staleness_p99"]
                                       for r in runs),
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
    return 0


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    raise SystemExit(main())
