#!/usr/bin/env python3
"""Readings that a cell's limits are set from, at the cell's own size.

    python3 bench/limits.py --workload <name> --seeds 12 --control-seeds 3

For each seed, one call of the system under test (the timed path: the
cell's configuration, mix and entry point) is compared with the plain
reference, as a run's check does: the lower readings.  For the first
``--control-seeds`` seeds the control, the reference computed in
bfloat16, is compared with it too: the upper readings.  One JSON line per
reading, then a summary line.  Runs on the accelerator; the benchmark's
own runs never run it.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import harness  # noqa: E402
from bench import traffic as tr  # noqa: E402
from bench.run import compare  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=4_000_000_000)
    args = ap.parse_args(argv)
    import jax
    import jax.numpy as jnp
    from bench.graph import load_graph
    from bench.reference.events import ref_graph
    from repro.launch.compile_cache import enable_compile_cache

    if jax.devices()[0].platform == "cpu":
        print("limits: needs an accelerator", file=sys.stderr)
        return 1
    enable_compile_cache()
    cell = harness.find_cell(harness.load_benchmark(ROOT), args.workload)
    algo, ref = harness.algo(cell.config), harness.reference(cell.config)
    graph = load_graph(cell.config)
    g = ref_graph(graph)
    events = cell.traffic.events_per_call
    lower, upper = [], []
    for k in range(args.seeds):
        seed = args.first_seed + k
        inputs = algo.make_inputs(cell.config, graph, tr.data_seed(seed))
        s = tr.call_seed(seed, 0)
        out = algo.System(cell.config, cell.traffic, graph, inputs).call(s)
        counters, hist = ref.run(g, inputs, cell.config, cell.traffic, s)
        got = compare(out, counters, hist, events)
        lower.append(got["theta_gap"])
        print(json.dumps(dict(seed=seed, side="program", **got)), flush=True)
        del out
        if k < args.control_seeds:
            c16, h16 = ref.run(g, inputs, cell.config, cell.traffic, s,
                               jnp.bfloat16)
            ctl = compare(dict(events=events, delivered=c16[0],
                               dropped=c16[1], invalid=c16[2],
                               theta_hist=h16), counters, hist, events)
            upper.append(ctl["theta_gap"])
            print(json.dumps(dict(seed=seed, side="control", **ctl)),
                  flush=True)
    print(json.dumps(dict(workload=args.workload,
                          lower=max(lower), upper=min(upper, default=None),
                          device=jax.devices()[0].device_kind)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
