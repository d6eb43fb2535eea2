#!/usr/bin/env python3
"""Run the gossip simulator's main path once on a TPU and check what it returns.

    python chip_smoke.py              # one chip: MP, CL-ADMM, serving, reference
    python chip_smoke.py --chips 4    # four chips: sharded MP and CL only

Every phase goes through the public entry point,
``repro.simulate.run_scenario(ScenarioSpec(...))``, at deployment size
(10^5-10^6 users with p = 64 models), checks its own result, and raises on
any failure.  The lines before the last are set-up facts per phase (compile
and wall seconds, sizes, device peak bytes, event counters) — not benchmark
metrics.  The last line is the verdict::

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

It exits non-zero, without a verdict, when JAX finds no TPU or when the
``repro`` package is not next to this file.  Everything runs in this one
process: a chip belongs to one process at a time.  The persistent compile
cache follows ``repro.launch.compile_cache`` (``JAX_COMPILATION_CACHE_DIR``
when set, else ``.jax_cache/`` in the checkout).
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

SEED = 0
# Sizes.  On the chip an (n, k_max, p) f32 slot array is tiled (8, 128) on
# its last two dims, so k_max = 17-18 pads to 24 and p = 64 to 128: 2.8x
# its packed size.  The v5e compiler's memory analysis (tests/
# test_chip_compile.py) then bounds one chip at about n = 500_000 for MP
# (~9.7 GiB per-op, ~13.3 GiB fused) and n = 100_000 for CL-ADMM
# (~14.3 GiB); CL at n = 150_000 is refused.  Wake-ups per round are a
# tenth of n in both.
# phase 1: MP gossip
MP_N, MP_ROUNDS, MP_BATCH = 500_000, 40, 50_000
# phase 2: CL-ADMM
CL_N, CL_ROUNDS, CL_BATCH = 100_000, 30, 10_000
# phase 3: inference requests per round served from the MP run's snapshots
SERVE_RATE = 2_500
# phase 4: chip vs host CPU under the plain reference impls
REF_N, REF_ROUNDS, REF_BATCH = 2_000, 40, 200
# four chips: sharded MP at the 10^6-user target and sharded CL at
# 200_000, a CL state one chip cannot hold.  Neither single-device run fits
# one chip, so the host CPU device of this process runs both comparisons.
SHARD_MP_N, SHARD_MP_ROUNDS, SHARD_MP_BATCH = 1_000_000, 20, 100_000
SHARD_CL_N, SHARD_CL_ROUNDS, SHARD_CL_BATCH = 200_000, 30, 20_000
DEGREE, P_DIM, RECORD_EVERY, ALPHA, MU, RHO = 8, 64, 10, 0.9, 0.1, 1.0

# Tolerances on O(1) f32 model rows.  The fused round telescopes the
# Eq. 6 update into scattered slot deltas (a different summation order
# than the per-op einsum), and the chip reduces in another order than the
# host CPU: both differ by f32 rounding only, which stays orders of
# magnitude below 1e-4 over these horizons.  (Sharded runs on one
# platform are bit-for-bit the single-device run; on four chips they are
# compared with the host CPU, so REF_ATOL holds them.)
FUSED_ATOL = 1e-4
REF_ATOL = 1e-4


class CompileClock:
    """Seconds JAX spent tracing, lowering and compiling, from its own
    monitoring events (0 for programs served by the persistent cache)."""

    def __init__(self):
        import jax
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **_):
        if event.startswith("/jax/core/compile/"):
            self.seconds += duration


def _check(cond: bool, what: str):
    if not cond:
        raise AssertionError(what)


def _peak_bytes() -> list:
    """``peak_bytes_in_use`` of every device of the default backend (-1
    where the device keeps no statistics)."""
    import jax
    return [int((d.memory_stats() or {}).get("peak_bytes_in_use", -1))
            for d in jax.devices()]


def _report(phase: str, **facts):
    print(json.dumps({"phase": phase, **facts}), flush=True)


def _timed(clock: CompileClock, fn, *args):
    """(result, wall seconds, compile seconds) of one blocking call."""
    c0, t0 = clock.seconds, time.perf_counter()
    out = fn(*args)
    return out, time.perf_counter() - t0, clock.seconds - c0


def _counters(tr) -> dict:
    return dict(delivered=int(tr.delivered), dropped=int(tr.dropped),
                events=int(tr.events), invalid=int(tr.invalid))


def _check_accounting(tr, what: str):
    c = _counters(tr)
    _check(c["delivered"] + c["dropped"] == 2 * (c["events"] - c["invalid"]),
           f"{what}: delivered + dropped != 2 * (events - invalid): {c}")
    _check(c["delivered"] > 0, f"{what}: nothing was delivered: {c}")


def _check_hist(tr, n: int, what: str):
    h = np.asarray(tr.theta_hist)
    _check(h.shape[1:] == (n, P_DIM),
           f"{what}: theta_hist shape {h.shape}, want (*, {n}, {P_DIM})")
    _check(bool(np.isfinite(h).all()), f"{what}: non-finite model rows")


def _maxdiff(a, b) -> float:
    return float(np.abs(np.asarray(a) - np.asarray(b)).max())


def _topology(n: int):
    from repro.simulate import random_geometric_topology
    t0 = time.perf_counter()
    topo = random_geometric_topology(n, k=DEGREE, seed=SEED)
    return topo, time.perf_counter() - t0


def mp_spec(topo, rounds: int, batch: int):
    """MP gossip under ``lossy-10`` with random solitary models/confidences."""
    from repro.simulate import ScenarioSpec, get_scenario
    rng = np.random.default_rng(SEED)
    sol = rng.standard_normal((topo.n, P_DIM), dtype=np.float32)
    c = rng.uniform(0.05, 1.0, topo.n).astype(np.float32)
    return ScenarioSpec(
        algo="mp", topology=topo, theta_sol=sol, c=c, alpha=ALPHA,
        conditions=get_scenario("lossy-10").make_conditions(rounds),
        rounds=rounds, batch=batch, seed=SEED, record_every=RECORD_EVERY)


def cl_spec(topo, rounds: int, batch: int):
    """CL-ADMM under ``churn-5`` with 3 quadratic-loss samples per agent,
    warm-started at the solitary means."""
    import jax.numpy as jnp
    from repro.core.losses import AgentData, solitary_mean
    from repro.simulate import ScenarioSpec, get_scenario
    rng = np.random.default_rng(SEED + 1)
    n = topo.n
    x = rng.standard_normal((n, 3, P_DIM), dtype=np.float32)
    x += rng.standard_normal((n, 1, P_DIM), dtype=np.float32)
    data = AgentData(jnp.asarray(x), jnp.zeros((n, 3), jnp.float32),
                     jnp.ones((n, 3), jnp.float32))
    sol = np.asarray(solitary_mean(data), np.float32)
    return ScenarioSpec(
        algo="cl", topology=topo, data=data, mu=MU, rho=RHO, theta_sol=sol,
        conditions=get_scenario("churn-5").make_conditions(rounds),
        rounds=rounds, batch=batch, seed=SEED, record_every=RECORD_EVERY)


def _run_twice(clock, spec):
    """Warm-up call (compile + run), then the timed call on warm programs.
    The warm-up trace is dropped at once: a CL trace holds its final ADMM
    state on the device, which must be free before the second call."""
    from repro.simulate import run_scenario
    first_s, compile_s = _timed(clock, run_scenario, spec)[1:]
    tr, wall_s, _ = _timed(clock, run_scenario, spec)
    return tr, dict(first_call_s=first_s, compile_s=compile_s, wall_s=wall_s)


def phase_mp(clock, n=MP_N, rounds=MP_ROUNDS, batch=MP_BATCH):
    """Phase 1: MP gossip, per-op round body and fused ``round_step``."""
    from repro.kernels.dispatch import ReproBackend
    topo, topo_s = _topology(n)
    spec = mp_spec(topo, rounds, batch)
    traces = {}
    for name, backend in (("per_op", None),
                          ("fused", ReproBackend.using(round_step="xla"))):
        tr, times = _run_twice(clock, dataclasses.replace(spec,
                                                          backend=backend))
        _check_accounting(tr, f"mp/{name}")
        _check_hist(tr, n, f"mp/{name}")
        traces[name] = tr
        _report(f"mp/{name}", n=n, k_max=topo.k_max, p=P_DIM, rounds=rounds,
                batch=batch, topology_s=topo_s, **times,
                peak_bytes=_peak_bytes(), **_counters(tr))
    a, b = traces["per_op"], traces["fused"]
    _check(_counters(a) == _counters(b),
           f"mp: fused counters {_counters(b)} != per-op {_counters(a)}")
    diff = _maxdiff(a.theta_hist, b.theta_hist)
    _report("mp/fused_vs_per_op", max_abs_diff=diff, atol=FUSED_ATOL)
    _check(diff <= FUSED_ATOL, f"mp: fused trajectory off by {diff}")
    return spec, a


def phase_cl(clock, n=CL_N, rounds=CL_ROUNDS, batch=CL_BATCH):
    """Phase 2: CL-ADMM with telemetry; the Eq. 7 objective must fall."""
    from repro.telemetry import TelemetryConfig
    topo, topo_s = _topology(n)
    spec = dataclasses.replace(cl_spec(topo, rounds, batch),
                               telemetry=TelemetryConfig(enabled=True))
    tr, times = _run_twice(clock, spec)
    _check_accounting(tr, "cl")
    _check_hist(tr, n, "cl")
    objective = [float(np.sum(o, dtype=np.float64))
                 for o in tr.telemetry.objective]
    _report("cl", n=n, k_max=topo.k_max, p=P_DIM, rounds=rounds, batch=batch,
            topology_s=topo_s, **times, peak_bytes=_peak_bytes(),
            objective=objective, **_counters(tr))
    _check(bool(np.all(np.isfinite(objective))), "cl: non-finite objective")
    _check(all(b < a for a, b in zip(objective, objective[1:])),
           f"cl: objective did not fall across record chunks: {objective}")


def phase_serve(clock, spec, unserved):
    """Phase 3: the serving plane on the MP run; serving never perturbs it."""
    from repro.simulate import precompute_serve_stream, run_scenario
    stream = precompute_serve_stream(spec.topology.n, spec.rounds,
                                     SERVE_RATE, seed=SEED)
    tr, wall_s, compile_s = _timed(
        clock, run_scenario, dataclasses.replace(spec, serve=stream))
    rep = tr.serve
    _report("serve", n=spec.topology.n, requests=int(rep.requests),
            hits=int(rep.hits), misses=int(rep.misses),
            invalidations=int(rep.invalidations), wall_s=wall_s,
            compile_s=compile_s, peak_bytes=_peak_bytes())
    _check(rep.requests == stream.n_requests,
           f"serve: {rep.requests} served of {stream.n_requests} requests")
    _check(rep.requests == rep.hits + rep.misses,
           f"serve: requests {rep.requests} != hits {rep.hits} + misses "
           f"{rep.misses}")
    _check(np.array_equal(tr.theta_hist, unserved.theta_hist),
           "serve: the served run's trajectory differs from the unserved one")


def phase_reference(clock, n=REF_N, rounds=REF_ROUNDS, batch=REF_BATCH):
    """Phase 4: the plain reference impls on the chip and on the host CPU
    device of this process must agree."""
    import jax
    from repro.kernels.dispatch import ReproBackend
    from repro.simulate import run_scenario
    topo, _ = _topology(n)
    ref = ReproBackend(default="reference")
    cpu = jax.devices("cpu")[0]
    for algo, make in (("mp", mp_spec), ("cl", cl_spec)):
        with jax.default_device(cpu):
            host = run_scenario(dataclasses.replace(
                make(topo, rounds, batch), backend=ref))
        chip, wall_s, compile_s = _timed(clock, run_scenario,
                                         dataclasses.replace(
                                             make(topo, rounds, batch),
                                             backend=ref))
        _check_accounting(chip, f"reference/{algo}")
        _check_hist(chip, n, f"reference/{algo}")
        diff = _maxdiff(chip.theta_hist, host.theta_hist)
        _report(f"reference/{algo}", n=n, wall_s=wall_s, compile_s=compile_s,
                max_abs_diff_vs_cpu=diff, atol=REF_ATOL, **_counters(chip))
        _check(_counters(chip) == _counters(host),
               f"reference/{algo}: chip counters {_counters(chip)} != host "
               f"{_counters(host)}")
        _check(diff <= REF_ATOL,
               f"reference/{algo}: chip vs host trajectory off by {diff}")


def phase_sharded(clock, n_devices=4, mp_n=SHARD_MP_N,
                  mp_rounds=SHARD_MP_ROUNDS, mp_batch=SHARD_MP_BATCH,
                  cl_n=SHARD_CL_N, cl_rounds=SHARD_CL_ROUNDS,
                  cl_batch=SHARD_CL_BATCH):
    """Four chips: sharded MP and CL under both halo exchanges, each
    against the single-device run of the same spec on the host CPU device
    of this process.  Peak bytes are reported for every chip, so a run
    that lands on chip 0 alone shows it."""
    import jax
    from repro.launch.sim_mesh import make_sim_mesh, mesh_shards
    from repro.simulate import greedy_partition, run_scenario
    mesh = make_sim_mesh(n_devices)
    _check(mesh_shards(mesh) == n_devices and mesh.devices.size == n_devices,
           f"sharded: mesh has {mesh.devices.size} devices, want {n_devices}")
    cpu = jax.devices("cpu")[0]
    for algo, make, n, rounds, batch in (
            ("mp", mp_spec, mp_n, mp_rounds, mp_batch),
            ("cl", cl_spec, cl_n, cl_rounds, cl_batch)):
        topo, topo_s = _topology(n)
        t0 = time.perf_counter()
        assignment = greedy_partition(topo, n_devices, seed=SEED)
        partition_s = time.perf_counter() - t0
        with jax.default_device(cpu):
            single, wall_s, compile_s = _timed(clock, run_scenario,
                                               make(topo, rounds, batch))
        _check_accounting(single, f"sharded/{algo}/single")
        want, want_counters = np.asarray(single.theta_hist), _counters(single)
        del single
        _report(f"sharded/{algo}/single_device", n=n, k_max=topo.k_max,
                p=P_DIM, rounds=rounds, batch=batch, device=cpu.platform,
                topology_s=topo_s, partition_s=partition_s, wall_s=wall_s,
                compile_s=compile_s)
        spec = make(topo, rounds, batch)
        for exchange in ("all_gather", "ring"):
            tr, wall_s, compile_s = _timed(
                clock, run_scenario,
                dataclasses.replace(spec, sharded=True, mesh=mesh,
                                    assignment=assignment,
                                    exchange=exchange))
            what = f"sharded/{algo}/{exchange}"
            _check_accounting(tr, what)
            _check_hist(tr, n, what)
            diff = _maxdiff(tr.theta_hist, want)
            _report(what, n=n, n_shards=tr.n_shards, overflow=tr.overflow,
                    halo_size=tr.halo_size, edge_cut=tr.edge_cut,
                    wall_s=wall_s, compile_s=compile_s,
                    max_abs_diff_vs_single=diff, bit_identical=diff == 0.0,
                    atol=REF_ATOL, peak_bytes=_peak_bytes(), **_counters(tr))
            _check(tr.n_shards == n_devices,
                   f"{what}: ran on {tr.n_shards} shards")
            _check(tr.overflow == 0, f"{what}: overflow {tr.overflow}")
            _check(_counters(tr) == want_counters,
                   f"{what}: counters {_counters(tr)} != single-device "
                   f"{want_counters}")
            _check(diff <= REF_ATOL,
                   f"{what}: trajectory off the single-device run by {diff}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded phase, on a 4-chip mesh")
    args = ap.parse_args(argv)
    try:
        import repro  # noqa: F401
    except ImportError as e:
        print(f"chip_smoke: the repro package is not next to this script "
              f"({e})", file=sys.stderr)
        return 2
    import jax
    from repro.launch.compile_cache import enable_compile_cache

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found {devices[0].platform!r} "
              f"devices only", file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX found "
              f"{len(devices)} device(s)", file=sys.stderr)
        return 1
    cache_dir = enable_compile_cache()
    clock = CompileClock()
    _report("setup", jax=jax.__version__, kind=devices[0].device_kind,
            devices=len(devices), compile_cache=cache_dir)
    if args.chips == 4:
        phase_sharded(clock)
    else:
        spec, per_op = phase_mp(clock)
        phase_cl(clock)
        phase_serve(clock, spec, per_op)
        phase_reference(clock)
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
