#!/usr/bin/env python3
"""Run one benchmark cell on the accelerator and print its result line.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Set-up (timed as ``setup_s``): imports, the configuration's graph (built
once and cached under ``bench/cache/``), its inputs drawn on the device
from ``--seed``, the system built, one warm-up call, which compiles or
loads every program the window runs from the persistent compile cache.

Window: a closed loop of calls to the system's public entry point
``run_scenario``, back to back, each one independent scenario whose
events are drawn from ``--seed`` and the call's index.  It stops issuing
calls after ``--seconds``; ``events_per_s`` is the wake-ups of all calls
completed over the wall time up to the end of the last one.  With
``--trace 1`` the window runs under the profiler and the result carries
the per-layer metrics read from the trace instead.

Check: one of the window's calls, drawn from ``--seed``, is rerun by
the configuration's plain reference (``bench/reference/``) once the window
has closed and the system's state is freed; the counters must match
exactly and the snapshots within the configuration's limit.  Each number
compared is printed beside its limit, on standard error and under
``checks`` in the result line.

The last line of standard output is one JSON object.  Without an
accelerator, or with fewer chips than the cell asks for, or without the
system under test beside ``bench/``, it exits non-zero and prints none.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

import numpy as np  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]
# the TPU runtime's logs stay inside the checkout, not under /tmp
os.environ.setdefault("TPU_LOG_DIR", str(ROOT / "bench" / "cache" / "tpu_logs"))

from bench import harness  # noqa: E402
from bench import traffic as tr  # noqa: E402

TRACE_DIR = ROOT / "bench" / "cache" / "trace"
SAMPLED_CALLS = 1


def _log(t_start: float, what: str):
    print(f"bench: {time.perf_counter() - t_start:9.3f} s  {what}",
          file=sys.stderr, flush=True)


def _peak_bytes(devices) -> int:
    """Peak bytes held on the fullest device: buffers in use, plus what
    executables reserved for their temporaries (a TPU keeps those out of
    ``peak_bytes_in_use``)."""
    def held(d):
        st = d.memory_stats() or {}
        return int(st.get("peak_bytes_in_use", 0)) \
            + int(st.get("peak_bytes_reserved", 0))
    return max(held(d) for d in devices)


class Sample:
    """Reservoir of ``k`` calls of the window, uniform over the calls
    completed, drawn from the run's seed."""

    def __init__(self, k: int, seed: int):
        self.k, self.rng, self.kept = k, np.random.default_rng(seed), []

    def offer(self, index: int, seed: int, out: dict):
        item = (index, seed, out)
        if len(self.kept) < self.k:
            self.kept.append(item)
        else:
            slot = int(self.rng.integers(0, index + 1))
            if slot < self.k:
                self.kept[slot] = item


def compare(out: dict, ref_counters, ref_hist, events: int) -> dict:
    """The numbers compared for one call: counter mismatches (exact) and
    the widest gap of a snapshot entry, over the reference's largest."""
    mism = abs(out["events"] - events)
    for k, v in zip(("delivered", "dropped", "invalid"), ref_counters):
        mism += abs(int(out[k]) - int(v))
    hist = np.asarray(out["theta_hist"], np.float32)
    if hist.shape != ref_hist.shape or not np.isfinite(hist).all():
        gap = float("inf")
    else:
        gap = float(np.max(np.abs(hist - ref_hist))
                    / max(float(np.max(np.abs(ref_hist))), 1e-30))
    return dict(counter_mismatch=mism, theta_gap=gap)


def check(cell, graph, inputs, sampled, dtype=None):
    """Rerun each sampled call with the plain reference.  Returns the
    worst of each compared number over them, each with its limit
    (``{name: {"value": v, "limit": l}}``), and how many calls failed."""
    import jax.numpy as jnp
    from bench.reference.events import ref_graph
    ref = harness.reference(cell.config)
    g = ref_graph(graph)
    limits = dict(counter_mismatch=0, **cell.config["limits"])
    worst = dict(counter_mismatch=0, theta_gap=0.0)
    failed = 0
    for index, seed, out in sampled:
        t0 = time.perf_counter()
        counters, hist = ref.run(g, inputs, cell.config, cell.traffic, seed,
                                 dtype or jnp.float32)
        _log(t0, f"reference of call {index}")
        got = compare(out, counters, hist, cell.traffic.events_per_call)
        failed += any(got[k] > limits[k] for k in got)
        worst = {k: max(worst[k], got[k]) for k in worst}
    return {k: dict(value=worst[k], limit=limits[k]) for k in worst}, failed


def run_cell(cell, seed: int, seconds: float, trace: bool, devices,
             t_start: float = T_START) -> dict:
    """Set up, run the window, check, and return the result object."""
    import jax
    from bench.graph import load_graph
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    # every program of the run, however quick to compile, comes from the
    # cache after the cell's first run
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    algo = harness.algo(cell.config)
    _log(t_start, "imports")
    graph = load_graph(cell.config)
    _log(t_start, "graph")
    inputs = algo.make_inputs(cell.config, graph, tr.data_seed(seed))
    jax.block_until_ready(inputs)
    system = algo.System(cell.config, cell.traffic, graph, inputs)
    _log(t_start, "inputs and system")
    system.call(tr.call_seed(seed, -1))          # warm-up: compiles or loads
    setup_s = time.perf_counter() - t_start
    _log(t_start, "warm-up call")

    sample = Sample(SAMPLED_CALLS, tr.sample_seed(seed))
    calls = []
    if trace:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0      # host spans only, no Python calls
        jax.profiler.start_trace(str(TRACE_DIR), profiler_options=opts)
    t0 = time.perf_counter()
    t_end = t0
    with jax.profiler.TraceAnnotation("bench/window"):
        while t_end - t0 < seconds:
            s = tr.call_seed(seed, len(calls))
            with jax.profiler.TraceAnnotation("bench/call"):
                out = system.call(s)
            t_end = time.perf_counter()
            sample.offer(len(calls), s, out)
            calls.append({k: out[k] for k in ("delivered", "dropped",
                                              "invalid")})
    if trace:
        jax.profiler.stop_trace()
    elapsed = t_end - t0
    peak = _peak_bytes(devices[:cell.workload["chips"]])
    _log(t_start, f"memory: {devices[0].memory_stats()}")
    del system, out
    gc.collect()

    _log(t_start, f"window: {len(calls)} calls in {elapsed:.3f} s")
    checks, failed = check(cell, graph, inputs, sample.kept)
    _log(t_start, "reference check")
    correct = failed == 0
    events = len(calls) * cell.traffic.events_per_call
    if trace:
        metrics, device, breakdown = traced_metrics(
            cell, graph, calls, devices[0].device_kind)
    else:
        metrics, device, breakdown = {}, {}, None
        values = dict(events_per_s=events / elapsed, setup_s=setup_s)
        for m in cell.end_to_end:
            metrics[m["name"]] = dict(value=values[m["name"]], unit=m["unit"])
    d0 = devices[0]
    result = dict(correct=correct, attempted=len(calls),
                  failed=failed, metrics=metrics,
                  device=dict(platform=d0.platform, kind=d0.device_kind,
                              count=len(devices), memory_peak_bytes=peak,
                              **device))
    if breakdown:
        result["breakdown"] = breakdown
    result["checks"] = checks
    return result


def traced_metrics(cell, graph, calls, device_kind):
    """Per-layer metrics, device busy/window seconds and the breakdown,
    from the trace of the window."""
    from bench import trace as trace_mod
    red = trace_mod.reduce(trace_mod.find(TRACE_DIR),
                           harness.algo(cell.config).SCAN_MODULE)
    ctx = trace_mod.Context(red, cell, int(graph.degree.max()), calls,
                            harness.work(cell.config), device_kind)
    metrics = {}
    for m in cell.per_layer:
        got = harness.metric_reader(m["name"])(ctx)
        if got is None:
            continue
        got = dict(got) if isinstance(got, dict) else dict(value=got)
        metrics[m["name"]] = dict(value=got.pop("value"), unit=m["unit"],
                                  **got)
    busy, window = red.busy_s(), red.window_s()
    return metrics, dict(busy_s=busy, window_s=window), red.breakdown()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        import repro  # noqa: F401
    except ImportError as e:
        print(f"bench: the system under test is not beside bench/ ({e})",
              file=sys.stderr)
        return 2
    cell = harness.find_cell(harness.load_benchmark(ROOT), args.workload)
    import jax
    devices = jax.devices()
    chips = cell.workload["chips"]
    if devices[0].platform == "cpu" or len(devices) < chips:
        print(f"bench: {args.workload} needs {chips} accelerator chip(s); "
              f"JAX found {len(devices)} {devices[0].platform} device(s)",
              file=sys.stderr)
        return 1
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                      devices)
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
