"""The four-chip CL-ADMM cell (``cl-knn200k-churn5-4chip``) at a small
size on the host CPU: ``correct`` holds for the sound program on four
fake devices and fails under the control and under a scan that leaves out
the halo exchange; its work counts and its readers, by hand."""

import json
import os
import subprocess
import sys

import pytest

from bench import harness, spans, trace
from bench.work import cl_sharded
from conftest import ROOT

CELL = "cl-knn200k-churn5-4chip"

# On four fake CPU devices (the device-count flag must precede jax's
# start, hence a process of its own), the committed cell with n cut to
# 2,000, run by ``run.run_cell`` (the look for chips skipped): the sound
# program; the control, the reference in bfloat16 in the program's place;
# and a scan whose exchange hands every shard the zero row where its halo
# should be.
SCRIPT = """
import json, os, sys, types
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
os.environ["JAX_PLATFORMS"] = "cpu"
sys.path[:0] = [{root!r}, {src!r}]
from pathlib import Path
import jax
import jax.numpy as jnp
from bench import graph, harness, run
from bench import traffic as tr
from bench.reference.events import ref_graph
from repro.simulate import partition

graph.CACHE = Path({cache!r})
bench = harness.load_benchmark(Path({root!r}))
cell = harness.find_cell(bench, {cell!r})
cell = cell._replace(
    config=dict(cell.config, n=2000, name="test-cl_sharded"),
    traffic=tr.load(Path({root!r}) / "bench" / "traffic"
                    / (cell.workload["traffic"] + ".json"), 2000))


def once():
    jax.clear_caches()
    res = run.run_cell(cell, 2 ** 33 + 5, 0.5, False, jax.devices())
    return dict(correct=res["correct"], failed=res["failed"],
                checks=res["checks"], attempted=res["attempted"])


out = dict(sound=once())

real_algo = harness.algo


class Control:
    def __init__(self, config, traffic, graph_, inputs):
        self.args = (ref_graph(graph_), inputs, config, traffic)
        self.ref = harness.reference(config)
        self.events = traffic.events_per_call

    def call(self, seed):
        (d, dr, inv), hist = self.ref.run(*self.args, seed, jnp.bfloat16)
        return dict(events=self.events, delivered=d, dropped=dr,
                    invalid=inv, theta_hist=hist)


harness.algo = lambda config: types.SimpleNamespace(
    make_inputs=real_algo(config).make_inputs, System=Control)
out["control"] = once()
harness.algo = real_algo


def no_halo(bnd, hsrc, hpos, n_halo, n_shards, exchange="all_gather",
            codec=None):
    def run_(x):
        return jnp.concatenate(
            [x, jnp.zeros((n_halo + 1,) + x.shape[1:], x.dtype)])
    return run_


partition.halo_exchange_fn = no_halo
out["no_halo"] = once()
print("RESULTS " + json.dumps(out))
"""


@pytest.fixture(scope="module")
def runs(tmp_path_factory):
    cache = tmp_path_factory.mktemp("cache")
    script = SCRIPT.format(root=str(ROOT), src=str(ROOT / "src"),
                           cache=str(cache), cell=CELL)
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    done = subprocess.run([sys.executable, "-c", script], env=env,
                          capture_output=True, text=True, timeout=900)
    assert done.returncode == 0, done.stderr[-3000:]
    line = [x for x in done.stdout.splitlines() if x.startswith("RESULTS ")]
    return json.loads(line[-1][len("RESULTS "):])


def test_sound_program_is_correct_on_four_devices(runs):
    res = runs["sound"]
    assert res["correct"] and res["failed"] == 0, res["checks"]
    assert res["checks"]["counter_mismatch"]["value"] == 0
    assert res["attempted"] >= 1


@pytest.mark.parametrize("fault", ["control", "no_halo"])
def test_broken_path_is_not_correct(runs, fault):
    res = runs[fault]
    assert not res["correct"] and res["failed"] > 0, res["checks"]


def test_reference_falls_back_to_the_host_when_the_chip_is_full(
        monkeypatch):
    """Where the first chip's memory cannot hold the reference, the same
    ``cl.run`` runs again on the host CPU device; other errors pass."""
    import jax
    from bench.reference import cl, cl_sharded
    seen = []

    def run(graph, inputs, *args):
        seen.append(jax.config.jax_default_device)
        if len(seen) == 1:
            raise jax.errors.JaxRuntimeError("RESOURCE_EXHAUSTED: 9 GB")
        return "counters", "hist"

    monkeypatch.setattr(cl, "run", run)
    got = cl_sharded.run({}, {}, {}, None, 1)
    assert got == ("counters", "hist")
    assert seen[0] is None and seen[1] == jax.devices("cpu")[0]

    def broken(*args):
        raise jax.errors.JaxRuntimeError("INVALID_ARGUMENT: no")

    monkeypatch.setattr(cl, "run", broken)
    with pytest.raises(jax.errors.JaxRuntimeError):
        cl_sharded.run({}, {}, {}, None, 1)


# --- work counts -------------------------------------------------------------

# the hand case of test_work.py: n = 10, B = 2, k_max = 3, p = 4, 6
# deliveries in 2 rounds, a snapshot every round
CASE = dict(n=10, batch=2, k_max=3, p=4, delivered=6, rounds=2,
            record_every=1)


def test_work_by_hand(monkeypatch):
    # the whole round: 528 FLOPs, 3156 bytes (test_work.py); a quarter of
    # it, 132 and 789, plus 2 + 1 payload rows of (1 + 9) * 4 = 40 words:
    # 120 words, 480 bytes
    monkeypatch.setattr(cl_sharded, "BOUNDARY_ROWS", 2)
    monkeypatch.setattr(cl_sharded, "HALO_ROWS", 1)
    assert cl_sharded.per_round(**CASE) == (132.0, 1269.0)


def test_shards_match_the_configuration():
    cell = harness.find_cell(harness.load_benchmark(ROOT), CELL)
    assert cl_sharded.SHARDS == cell.config["shards"] == 4
    assert cell.workload["chips"] == cell.config["shards"]


def test_halo_rows_are_the_configurations_partition():
    """BOUNDARY_ROWS and HALO_ROWS are the largest boundary and halo of
    the configuration's greedy partition, as the system lays it out (the
    graph and partition at n = 200,000: about 30 s on the host)."""
    from bench import graph
    from bench.algos.cl_sharded import load_assignment
    from repro.simulate import GraphPartition
    cell = harness.find_cell(harness.load_benchmark(ROOT), CELL)
    g = graph.load_graph(cell.config)
    topo = graph.load_topology(cell.config, g)
    part = GraphPartition.build(topo, load_assignment(cell.config, topo),
                                cell.config["shards"])
    assert (part.boundary_size, part.halo_size) \
        == (cl_sharded.BOUNDARY_ROWS, cl_sharded.HALO_ROWS)
    # a second load reads the cached assignment
    assert (load_assignment(cell.config, topo) == part.owner).all()


# --- readers -----------------------------------------------------------------


def _ctx(tmp_path, monkeypatch, scan_args):
    """A window of two calls of 4 rounds each on one chip: exchange ops
    of 3 and 1 us in the first call's scan, 2 us in the second's."""
    import gzip
    monkeypatch.setattr(spans, "TRACE_DIR", tmp_path)
    meta = [
        dict(ph="M", pid=3, name="process_name",
             args=dict(name="/device:TPU:0")),
        dict(ph="M", pid=3, tid=2, name="thread_name",
             args=dict(name="XLA Modules")),
        dict(ph="M", pid=3, tid=3, name="thread_name",
             args=dict(name="XLA Ops")),
        dict(ph="M", pid=9, name="process_name", args=dict(name="/host:CPU")),
    ]

    def x(pid, tid, name, ts, dur, **args):
        return dict(ph="X", pid=pid, tid=tid, name=name, ts=ts, dur=dur,
                    args=args)

    body = "jit(_sharded_cl_scan)/shard_map/while/body/"
    ev = [x(9, 1, "bench/window", 0, 200)]
    for t, args in zip((0, 100), scan_args):
        ev += [x(9, 1, "bench/call", t, 90),
               x(9, 1, "repro/scenario", t + 1, 88),
               x(9, 1, "repro/host/scan", t + 20, 2, **args),
               x(3, 2, "jit__sharded_cl_scan(1)", t + 25, 40)]
    ev += [x(3, 3, "all-gather.1", 30, 3, hlo_category="collective",
             tf_op=body + "repro/phase/exchange/all_gather:"),
           x(3, 3, "fusion.2", 40, 1, hlo_category="loop fusion",
             tf_op=body + "repro/phase/exchange/concatenate:"),
           x(3, 3, "fusion.3", 50, 4, hlo_category="loop fusion",
             tf_op=body + "repro/phase/edge/scatter:"),
           x(3, 3, "all-gather.1", 130, 2, hlo_category="collective",
             tf_op=body + "repro/phase/exchange/all_gather:")]
    p = tmp_path / "plugins/profile/1/h.trace.json.gz"
    p.parent.mkdir(parents=True)
    with gzip.open(p, "wt") as f:
        json.dump(dict(traceEvents=meta + ev), f)
    cell = harness.find_cell(harness.load_benchmark(ROOT), CELL)
    cell = cell._replace(traffic=cell.traffic._replace(rounds=4))
    return trace.Context(trace.reduce(p, "_sharded_cl_scan"), cell, 17, [],
                         harness.work(cell.config), "TPU v5 lite")


def test_exchange_readers_by_hand(tmp_path, monkeypatch):
    """6 us of exchange over 8 rounds; 28,163,200 and 28,163,000 bytes a
    round stated by the two calls' scan spans."""
    ctx = _ctx(tmp_path, monkeypatch, [dict(halo_bytes_per_round="28163200"),
                                       dict(halo_bytes_per_round="28163000")])
    read = harness.metric_reader
    assert read("phase_ms_per_round.exchange")(ctx) == pytest.approx(7.5e-4)
    assert read("halo_mb_per_round")(ctx) == pytest.approx(28.1631)


def test_halo_reader_is_silent_without_the_arg(tmp_path, monkeypatch):
    """A program whose scan spans state no halo bytes (the parent of the
    sharded warm start) reports nothing, and does not raise."""
    ctx = _ctx(tmp_path, monkeypatch, [{}, {}])
    assert harness.metric_reader("halo_mb_per_round")(ctx) is None
