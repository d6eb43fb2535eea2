"""Backend-dispatch benchmark: time every available implementation of the
MP-mix and ADMM-primal hot loops (plus the sparse gather-mix and the fused
``round_step`` gossip round at B = 64/512/4096 event batches) and write a
``BENCH_dispatch.json`` with per-backend timings and parity errors.

    PYTHONPATH=src python benchmarks/bench_dispatch.py            # full
    PYTHONPATH=src python benchmarks/bench_dispatch.py --smoke    # CI lane

``--smoke`` shrinks shapes and forces the Pallas implementations through
interpret mode so backend-parity regressions surface in CI even on CPU
runners (interpret timings are NOT perf numbers — the maxerr columns are
the point).  Off-TPU without ``--smoke``/``--interpret``, Pallas impls are
recorded as skipped.

``--baseline BENCH_dispatch.baseline.json`` turns the run into a CI gate:
it fails on parity drift (any op/impl maxerr above 1e-5 AND 10x its
baseline) and on >2x per-op slowdown.  Slowdowns are normalized by the
median slowdown across all timed (op, impl) pairs, so a uniformly slower
runner doesn't trip the gate — only ops that regressed *relative to the
rest of the suite* do.  Pallas interpret timings are never gated (they are
validation artifacts, not perf numbers).  Refresh the committed baseline
with ``--smoke --out BENCH_dispatch.baseline.json`` when op timings shift
on purpose.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from common import time_call  # noqa: E402

from repro.kernels import dispatch  # noqa: E402
from repro.kernels.dispatch import ReproBackend, resolve  # noqa: E402


def _time_loop(fn, repeats: int) -> float:
    """Shared min-of-repeats estimator, synced through the device queue."""
    return time_call(fn, repeats=repeats, sync=jax.block_until_ready)


def _runnable_impls(op: str, interpret: bool):
    """(impl, backend, note) triples for every registered implementation."""
    out = []
    for name in dispatch.implementations(op):
        backend = ReproBackend.using(
            interpret=interpret or None, **{op: name})
        if dispatch.available(op, name, interpret=interpret):
            out.append((name, backend, None))
        else:
            out.append((name, None,
                        "needs TPU (or --interpret/--smoke for the slow "
                        "interpret mode)"))
    return out


def _maxerr(got, want) -> float:
    ga = got if isinstance(got, (tuple, list)) else (got,)
    wa = want if isinstance(want, (tuple, list)) else (want,)
    return max(float(jnp.abs(jnp.asarray(g, jnp.float32)
                             - jnp.asarray(w, jnp.float32)).max())
               for g, w in zip(ga, wa))


def bench_mix(smoke: bool, interpret: bool, repeats: int) -> dict:
    n, D = (16, 2048) if smoke else (32, 65536)
    loops = 5 if smoke else 50
    k1, k2, k3, k4 = jax.random.split(jax.random.PRNGKey(0), 4)
    theta = jax.random.normal(k1, (n, D))
    sol = jax.random.normal(k2, (n, D))
    A = jax.random.uniform(k3, (n, n)) / n
    b = jax.random.uniform(k4, (n,))
    want = resolve("mix", ReproBackend.using(mix="reference"))(
        theta, sol, A, b)
    impls = {}
    for name, backend, skip in _runnable_impls("mix", interpret):
        if skip:
            impls[name] = {"skipped": skip}
            continue
        mix = resolve("mix", backend)
        loop = jax.jit(lambda th, m=mix: jax.lax.scan(
            lambda t, _: (m(t, sol, A, b), None), th, None, length=loops)[0])
        impls[name] = {
            "maxerr": _maxerr(mix(theta, sol, A, b), want),
            "us_per_loop": _time_loop(lambda: loop(theta), repeats),
            "loop_iters": loops,
        }
    return {"shape": {"n": n, "D": D}, "impls": impls}


def bench_sparse_mix(smoke: bool, interpret: bool, repeats: int) -> dict:
    n, k, p = (256, 8, 64) if smoke else (4096, 16, 256)
    loops = 5 if smoke else 50
    rng = np.random.default_rng(0)
    idx = jnp.asarray(rng.integers(0, n, (n, k)), jnp.int32)
    w = jnp.asarray(rng.uniform(0, 1, (n, k)), jnp.float32)
    b = jnp.asarray(rng.uniform(0, 1, n), jnp.float32)
    table = jnp.asarray(rng.standard_normal((n, p)), jnp.float32)
    sol = jnp.asarray(rng.standard_normal((n, p)), jnp.float32)
    want = resolve("sparse_mix", ReproBackend.using(
        sparse_mix="reference"))(table, idx, w, b, sol)
    impls = {}
    for name, backend, skip in _runnable_impls("sparse_mix", interpret):
        if skip:
            impls[name] = {"skipped": skip}
            continue
        mix = resolve("sparse_mix", backend)
        loop = jax.jit(lambda t, m=mix: jax.lax.scan(
            lambda tt, _: (m(tt, idx, w, b, sol), None), t, None,
            length=loops)[0])
        impls[name] = {
            "maxerr": _maxerr(mix(table, idx, w, b, sol), want),
            "us_per_loop": _time_loop(lambda: loop(table), repeats),
            "loop_iters": loops,
        }
    return {"shape": {"n": n, "k": k, "p": p}, "impls": impls}


def bench_admm_primal(smoke: bool, interpret: bool, repeats: int) -> dict:
    n, k, p = (32, 8, 32) if smoke else (256, 16, 512)
    loops = 5 if smoke else 50
    rng = np.random.default_rng(1)
    w = jnp.asarray(rng.uniform(0.1, 1, (n, k)), jnp.float32)
    live = jnp.asarray(rng.uniform(size=(n, k)) < 0.8)
    zo, zn, lo, ln = (jnp.asarray(rng.standard_normal((n, k, p)), jnp.float32)
                      for _ in range(4))
    D = jnp.asarray(rng.uniform(1, 4, n), jnp.float32)
    m = jnp.asarray(rng.integers(1, 100, n), jnp.float32)
    sx = jnp.asarray(rng.standard_normal((n, p)), jnp.float32)
    mu, rho = 0.05, 1.0

    def batched(primal, name=""):
        if name.endswith("_sharded"):
            # sharded impls take the stacked batched form natively
            return lambda *a: primal(*a, mu, rho)
        return jax.vmap(lambda w_, l_, a, b_, c_, d_, D_, m_, s_:
                        primal(w_, l_, a, b_, c_, d_, D_, m_, s_, mu, rho))

    want = batched(resolve("admm_primal", ReproBackend.using(
        admm_primal="reference")))(w, live, zo, zn, lo, ln, D, m, sx)
    impls = {}
    for name, backend, skip in _runnable_impls("admm_primal", interpret):
        if skip:
            impls[name] = {"skipped": skip}
            continue
        primal = batched(resolve("admm_primal", backend), name)

        def body(carry, _, primal=primal):
            zo_, zn_ = carry
            theta_l, theta_js = primal(w, live, zo_, zn_, lo, ln, D, m, sx)
            # feed the solution back so the loop has a real dependency chain
            return (0.9 * zo_ + 0.1 * theta_js,
                    0.9 * zn_ + 0.1 * theta_l[:, None, :]), None

        loop = jax.jit(lambda z, body=body: jax.lax.scan(
            body, z, None, length=loops)[0][0])
        impls[name] = {
            "maxerr": _maxerr(primal(w, live, zo, zn, lo, ln, D, m, sx),
                              want),
            "us_per_loop": _time_loop(lambda: loop((zo, zn)), repeats),
            "loop_iters": loops,
        }
    return {"shape": {"n": n, "k": k, "p": p}, "impls": impls}


def bench_admm_edge(smoke: bool, interpret: bool, repeats: int) -> dict:
    # smoke shape sized so the timed loop is comparable to the other ops'
    # (sub-100us loops are pure dispatch noise and destabilize the gate)
    E, p = (512, 64) if smoke else (4096, 256)
    loops = 5 if smoke else 50
    rng = np.random.default_rng(2)
    args = tuple(jnp.asarray(rng.standard_normal((E, p)), jnp.float32)
                 for _ in range(8))
    rho = 1.3
    want = resolve("admm_edge", ReproBackend.using(
        admm_edge="reference"))(*args, rho=rho)
    impls = {}
    for name, backend, skip in _runnable_impls("admm_edge", interpret):
        if skip:
            impls[name] = {"skipped": skip}
            continue
        edge = resolve("admm_edge", backend)

        def body(carry, _, edge=edge):
            t_ii, l_own_i = carry
            out = edge(t_ii, *args[1:4], l_own_i, *args[5:], rho=rho)
            # feed z_i / the updated dual back for a real dependency chain
            return (0.9 * t_ii + 0.1 * out[0], out[2]), None

        loop = jax.jit(lambda c, body=body: jax.lax.scan(
            body, c, None, length=loops)[0][0])
        impls[name] = {
            "maxerr": _maxerr(edge(*args, rho=rho), want),
            "us_per_loop": _time_loop(lambda: loop((args[0], args[4])),
                                      repeats),
            "loop_iters": loops,
        }
    return {"shape": {"E": E, "p": p}, "impls": impls}


def bench_edge_reweight(smoke: bool, interpret: bool, repeats: int) -> dict:
    n, k = (512, 8) if smoke else (8192, 16)
    loops = 5 if smoke else 50
    rng = np.random.default_rng(3)
    live = jnp.asarray(rng.uniform(size=(n, k)) < 0.8)
    w0 = rng.uniform(0, 1, (n, k)) * np.asarray(live)
    w0 = jnp.asarray(w0 / np.maximum(w0.sum(axis=1, keepdims=True), 1e-9),
                     jnp.float32)
    d = jnp.asarray(rng.uniform(0, 4, (n, k)), jnp.float32)
    eta, lam = 0.3, 1.0
    want = resolve("edge_reweight", ReproBackend.using(
        edge_reweight="reference"))(d, w0, live, eta=eta, lam=lam)
    impls = {}
    for name, backend, skip in _runnable_impls("edge_reweight", interpret):
        if skip:
            impls[name] = {"skipped": skip}
            continue
        rw = resolve("edge_reweight", backend)

        def body(w, _, rw=rw):
            # feed the learned weights back for a real dependency chain
            return rw(d, w, live, eta=eta, lam=lam), None

        loop = jax.jit(lambda w, body=body: jax.lax.scan(
            body, w, None, length=loops)[0])
        impls[name] = {
            "maxerr": _maxerr(rw(d, w0, live, eta=eta, lam=lam), want),
            "us_per_loop": _time_loop(lambda: loop(w0), repeats),
            "loop_iters": loops,
        }
    return {"shape": {"n": n, "k": k}, "impls": impls}


def bench_round_step(smoke: bool, interpret: bool, repeats: int,
                     batch: int) -> dict:
    """The fused MP gossip round (DESIGN.md §15) at one event-batch size.

    The timed loop prefetches each round's operands from the carried flat
    slot table (``round_prefetch``) and feeds ``(theta, Ke, got_ever)``
    back through the same event batch — exactly the scenario engines' scan
    carry — so us_per_loop / loop_iters is the per-round cost the engine
    pays at this batch size.
    """
    from repro.kernels.dispatch import round_prefetch, round_scales
    n, k, p = (2048, 8, 16) if smoke else (10000, 8, 32)
    loops = 5 if smoke else 50
    rng = np.random.default_rng(4)
    f32 = jnp.float32
    K = jnp.asarray(rng.standard_normal((n, k, p)), f32)
    Ke = dispatch.encode_slots(K)
    nbr_p = jnp.asarray(rng.uniform(0, 1, (n, k)), f32)
    theta = jnp.asarray(rng.standard_normal((n, p)), f32)
    got0 = jnp.zeros((n,), bool)
    base = jnp.asarray(rng.standard_normal((n, p)), f32)
    c = jnp.asarray(rng.uniform(0.1, 1, n), f32)
    a_w = round_scales(nbr_p, c, alpha=0.9)
    # collision-free targets: duplicate winners are realization-dependent
    # (see round_fuse docstring), which would read as parity drift here
    codes = rng.choice(n * k, size=2 * batch, replace=False)
    ev = (jnp.asarray(codes[batch:] // k, jnp.int32),
          jnp.asarray(codes[:batch] // k, jnp.int32),
          jnp.asarray(codes[batch:] % k, jnp.int32),
          jnp.asarray(codes[:batch] % k, jnp.int32),
          jnp.asarray(rng.uniform(size=batch) < 0.8),
          jnp.asarray(rng.uniform(size=batch) < 0.8),
          jnp.asarray(rng.uniform(size=batch) < 0.2),
          jnp.asarray(rng.uniform(size=batch) < 0.2))
    ops0 = round_prefetch(theta, theta, Ke, *ev)
    want = resolve("round_step", ReproBackend.using(
        round_step="reference"))(theta, Ke, got0, *ops0, base, a_w)
    impls = {}
    for name, backend, skip in _runnable_impls("round_step", interpret):
        if skip is None and name == "pallas" and batch > 512 \
                and jax.default_backend() != "tpu":
            # the interpret-mode event loop is ~seconds per round here;
            # parity is already pinned at B <= 512 and in tests/
            skip = "interpret mode too slow at this batch (parity covered " \
                   "at B <= 512)"
        if skip:
            impls[name] = {"skipped": skip}
            continue
        step = resolve("round_step", backend)

        def body(carry, _, step=step):
            th, ke, go = carry
            th2, ke2, go2, _ = step(th, ke, go,
                                    *round_prefetch(th, th, ke, *ev),
                                    base, a_w)
            return (th2, ke2, go2), None

        loop = jax.jit(lambda s0, body=body: jax.lax.scan(
            body, s0, None, length=loops)[0][0])
        impls[name] = {
            "maxerr": _maxerr(step(theta, Ke, got0, *ops0, base, a_w)[:2],
                              want[:2]),
            "us_per_loop": _time_loop(lambda: loop((theta, Ke, got0)),
                                      repeats),
            "loop_iters": loops,
        }
    return {"shape": {"n": n, "k": k, "p": p, "B": batch}, "impls": impls}


PARITY_FLOOR = 1e-5          # drift below this is float noise, never gated
MAX_SLOWDOWN = 2.0           # vs baseline, after machine-speed normalization


def _is_gated_timing(op: str, impl: str) -> bool:
    """Pallas interpret-mode timings are validation artifacts, not perf."""
    import re

    from repro.kernels.dispatch import _REGISTRY
    op = re.sub(r"_b\d+$", "", op)     # round_step_b512 -> round_step
    entry = _REGISTRY.get(op, {}).get(impl)
    return entry is not None and not entry.pallas


def compare_to_baseline(report: dict, baseline: dict) -> list:
    """Gate failures of ``report`` vs a committed baseline (see module
    docstring for the rules).  Returns human-readable failure strings."""
    failures = []
    pairs = []               # (op, impl, cur_us, base_us)
    for op, entry in report["ops"].items():
        base_op = baseline.get("ops", {}).get(op, {}).get("impls", {})
        for impl, row in entry["impls"].items():
            base = base_op.get(impl)
            if "maxerr" not in row or base is None or "maxerr" not in base:
                continue
            if row["maxerr"] > max(10.0 * base["maxerr"], PARITY_FLOOR):
                failures.append(
                    f"parity drift: {op}/{impl} maxerr {row['maxerr']:.2e} "
                    f"vs baseline {base['maxerr']:.2e}")
            if _is_gated_timing(op, impl):
                pairs.append((op, impl, row["us_per_loop"],
                              base["us_per_loop"]))
    if pairs:
        slowdowns = sorted(c / max(b, 1e-9) for _, _, c, b in pairs)
        machine = slowdowns[len(slowdowns) // 2]        # median = runner speed
        for op, impl, cur, base in pairs:
            rel = (cur / max(base, 1e-9)) / max(machine, 1e-9)
            if rel > MAX_SLOWDOWN:
                failures.append(
                    f"slowdown: {op}/{impl} {cur:.1f}us vs baseline "
                    f"{base:.1f}us ({rel:.2f}x the suite median drift)")
    return failures


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="tiny shapes + interpret-mode Pallas (CI parity lane)")
    ap.add_argument("--interpret", action="store_true",
                    help="include Pallas impls via interpret mode off-TPU")
    ap.add_argument("--repeats", type=int, default=None)
    ap.add_argument("--out", default="BENCH_dispatch.json")
    ap.add_argument("--baseline", default=None,
                    help="committed baseline JSON to gate against (fail on "
                         "parity drift or >2x normalized slowdown)")
    args = ap.parse_args(argv)
    # gating needs stable medians; plain smoke stays cheap
    repeats = args.repeats or (5 if args.baseline or not args.smoke else 1)
    interpret = args.smoke or args.interpret

    ops = {
        "mix": bench_mix(args.smoke, interpret, repeats),
        "sparse_mix": bench_sparse_mix(args.smoke, interpret, repeats),
        "admm_primal": bench_admm_primal(args.smoke, interpret, repeats),
        "admm_edge": bench_admm_edge(args.smoke, interpret, repeats),
        "edge_reweight": bench_edge_reweight(args.smoke, interpret, repeats),
        # the fused gossip round across engine-realistic batch sizes
        # (n // 10 wake-ups per round at n = 640 / 5k / 40k)
        **{f"round_step_b{B}": bench_round_step(args.smoke, interpret,
                                                repeats, B)
           for B in (64, 512, 4096)},
    }
    report = {
        "meta": {
            "platform": jax.default_backend(),
            "jax": jax.__version__,
            "smoke": args.smoke,
            "interpret": interpret,
            "repeats": repeats,
        },
        "ops": ops,
    }

    worst = 0.0
    for op, entry in report["ops"].items():
        for impl, row in entry["impls"].items():
            if "maxerr" in row:
                worst = max(worst, row["maxerr"])
                print(f"bench_dispatch,{op},{impl},"
                      f"us={row['us_per_loop']:.1f},maxerr={row['maxerr']:.2e}",
                      flush=True)
            else:
                print(f"bench_dispatch,{op},{impl},skipped", flush=True)
    report["meta"]["worst_maxerr"] = worst

    with open(args.out, "w") as f:
        json.dump(report, f, indent=2)
    print(f"wrote {args.out}")
    if worst > 1e-4:
        print(f"PARITY FAILURE: worst maxerr {worst:.2e} > 1e-4")
        return 1
    if args.baseline:
        with open(args.baseline) as f:
            baseline = json.load(f)
        failures = compare_to_baseline(report, baseline)
        for fail in failures:
            print(f"BASELINE FAILURE: {fail}")
        if failures:
            return 1
        print(f"baseline gate OK vs {args.baseline}")
    return 0


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    raise SystemExit(main())
