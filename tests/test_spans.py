"""Profiler spans and phase scopes of the scenario engines
(``repro.simulate.spans``, DESIGN.md §14): every jitted scan carries the
``repro/phase/<phase>`` scopes of its round (the sharded scans on four
fake devices, in a subprocess), and a ``run_scenario`` call under the
profiler writes ``repro/scenario`` with its host steps nested in order,
their ``bytes`` args equal to the arrays copied."""

import glob
import gzip
import json
import os
import re
import subprocess
import sys

import jax
import numpy as np
import pytest

from repro.core.losses import AgentData, pad_datasets, solitary_mean
from repro.core.sparse import to_device
from repro.kernels.dispatch import ReproBackend
from repro.simulate import (NetworkConditions, ScenarioSpec,
                            precompute_serve_stream,
                            random_geometric_topology, run_scenario)
from repro.simulate import engines, scheduler
from repro.telemetry.config import TelemetryConfig

COND = NetworkConditions(drop_prob=0.15, stale_prob=0.2, churn_rate=0.01)
RUN_KW = dict(rounds=8, batch=8, seed=5, record_every=4)
PHASE = re.compile(r"repro/phase/(\w+)/")
MP = {"init", "draw", "comm", "update", "record"}
CL = {"init", "primal", "edge", "record"}


@pytest.fixture(scope="module")
def problem():
    n = 60
    topo = random_geometric_topology(n, k=4, seed=0)
    rng = np.random.default_rng(0)
    xs = [rng.standard_normal((int(rng.integers(1, 8)), 3))
          for _ in range(n)]
    data = pad_datasets(xs, [np.zeros(len(x)) for x in xs])
    # host arrays, so the upload counts them
    data = AgentData(*(np.asarray(a) for a in (data.x, data.y, data.mask)))
    sol = np.asarray(solitary_mean(data), np.float32)
    c = np.full(n, 0.8, np.float32)
    return topo, data, sol, c


def _spec(problem, algo, **kw):
    topo, data, sol, c = problem
    base = dict(algo=algo, topology=topo, conditions=COND, **RUN_KW)
    if algo == "cl":
        base.update(data=data, mu=0.1, rho=1.0, theta_sol=sol)
    else:
        base.update(theta_sol=sol, c=c, alpha=0.9)
    base.update(kw)
    return ScenarioSpec(**base)


def _lowered(monkeypatch, module, name, spec):
    """Run the spec with ``module.name`` (a jitted function) spied on, and
    return the lowered text of its calls, locations included."""
    fn = getattr(module, name)
    texts = []

    def spy(*args, **kwargs):
        texts.append(fn.lower(*args, **kwargs).as_text(debug_info=True))
        return fn(*args, **kwargs)

    monkeypatch.setattr(module, name, spy)
    run_scenario(spec)
    assert texts, f"{name} was not called"
    return "".join(texts)


TEL = TelemetryConfig(enabled=True)
FUSED = ReproBackend.using(round_step="xla")
LOWERED = [
    ("mp", {}, engines, "_scenario_scan", MP),
    ("mp", dict(telemetry=TEL), engines, "_scenario_scan", MP),
    ("mp", dict(backend=FUSED), engines, "_scenario_scan", MP),
    ("mp", dict(backend=FUSED, telemetry=TEL), engines, "_scenario_scan",
     MP),
    ("cl", {}, engines, "_cl_scenario_scan", CL - {"record"}),
    ("cl", dict(telemetry=TEL), engines, "_cl_scenario_scan", CL),
    ("cl", {}, scheduler, "_draw_stream", {"draw"}),
    ("joint", dict(eta_graph=0.3, graph_every=2, prune_eps=1e-3,
                   telemetry=TEL),
     engines, "_joint_scenario_scan", {"comm", "update", "graph", "record"}),
]


@pytest.mark.parametrize("algo,kw,module,name,phases", LOWERED,
                         ids=[f"{a}-{n}-{'-'.join(sorted(k)) or 'default'}"
                              for a, k, _, n, _ in LOWERED])
def test_scan_lowering_holds_its_phases(monkeypatch, problem, algo, kw,
                                        module, name, phases):
    """Each jitted scan's lowered text holds exactly the phase scopes its
    round has (the snapshot's record phase in CL exists only with
    telemetry: without it the snapshot is the carry itself, no op)."""
    text = _lowered(monkeypatch, module, name, _spec(problem, algo, **kw))
    assert set(PHASE.findall(text)) == phases


def test_dispatch_scopes_nest_inside_phases(monkeypatch, problem):
    """The ``repro/<op>/<impl>`` dispatch scopes keep their names inside
    the phase scopes, so the ``op_ms_per_round.*`` readers, which match by
    substring, read the same ops."""
    text = _lowered(monkeypatch, engines, "_scenario_scan",
                    _spec(problem, "mp"))
    assert re.search(r"repro/phase/update/[^\"]*repro/neighbor_aggregate/",
                     text)


# --- the sharded scans, on four fake devices --------------------------------

SHARDED = """
import json, os, re, sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
os.environ["JAX_PLATFORMS"] = "cpu"
sys.path.insert(0, {src!r})
import jax
import numpy as np
from repro.core.losses import pad_datasets, solitary_mean
from repro.simulate import (NetworkConditions, ScenarioSpec,
                            random_geometric_topology, run_scenario)
from repro.simulate import partition
from repro.telemetry.config import TelemetryConfig

assert jax.device_count() == 4
n = 80
topo = random_geometric_topology(n, k=4, seed=0)
rng = np.random.default_rng(0)
xs = [rng.standard_normal((int(rng.integers(1, 8)), 3)) for _ in range(n)]
data = pad_datasets(xs, [np.zeros(len(x)) for x in xs])
sol = np.asarray(solitary_mean(data), np.float32)
kw = dict(topology=topo, conditions=NetworkConditions(drop_prob=0.1,
          stale_prob=0.2), rounds=4, batch=8, seed=1, record_every=2,
          sharded=True, n_shards=4, telemetry=TelemetryConfig(enabled=True))
specs = dict(
    _sharded_scenario_scan=ScenarioSpec(algo="mp", theta_sol=sol,
                                        c=np.full(n, 0.8, np.float32),
                                        alpha=0.9, **kw),
    _sharded_cl_scan=ScenarioSpec(algo="cl", data=data, mu=0.1, rho=1.0,
                                  theta_sol=sol, **kw),
    _sharded_joint_scan=ScenarioSpec(algo="joint", theta_sol=sol,
                                     c=np.full(n, 0.8, np.float32),
                                     alpha=0.9, eta_graph=0.3,
                                     graph_every=2, **kw))
out = {{}}
for name, spec in specs.items():
    fn = getattr(partition, name)
    texts = []
    def spy(*a, fn=fn, texts=texts, **k):
        texts.append(fn.lower(*a, **k).as_text(debug_info=True))
        return fn(*a, **k)
    setattr(partition, name, spy)
    run_scenario(spec)
    setattr(partition, name, fn)
    out[name] = sorted(set(re.findall(r"repro/phase/(\\w+)/",
                                      "".join(texts))))
print("PHASES " + json.dumps(out))
"""


@pytest.fixture(scope="module")
def sharded_phases():
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    done = subprocess.run([sys.executable, "-c",
                           SHARDED.format(src=os.path.abspath(src))],
                          env=env, capture_output=True, text=True,
                          timeout=600)
    assert done.returncode == 0, done.stderr[-3000:]
    line = [x for x in done.stdout.splitlines() if x.startswith("PHASES ")]
    return json.loads(line[-1][len("PHASES "):])


@pytest.mark.parametrize("name,phases", [
    ("_sharded_scenario_scan", MP | {"exchange"}),
    ("_sharded_cl_scan", {"init", "draw", "primal", "exchange", "edge",
                          "record"}),
    ("_sharded_joint_scan", {"draw", "comm", "update", "graph", "record",
                             "exchange"}),
])
def test_sharded_scan_lowering_holds_its_phases(sharded_phases, name,
                                                phases):
    """The sharded scans carry the single-device phases (their draw phase
    selects the shard's events from the replayed stream) plus
    ``repro/phase/exchange`` around the halo exchange."""
    assert set(sharded_phases[name]) == phases


# --- host spans under the profiler (CPU) ------------------------------------


def _traced(tmp_path, spec):
    """Run the spec under the profiler; its result and the host spans of
    the trace (name, start, end, args)."""
    jax.profiler.start_trace(str(tmp_path))
    try:
        tr = run_scenario(spec)
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(str(tmp_path / "plugins/profile/*/*.trace.json.gz"))
    with gzip.open(path, "rt") as f:
        events = json.load(f)["traceEvents"]
    spans = sorted((e["ts"], e["ts"] + e["dur"], e["name"], e.get("args", {}))
                   for e in events if e.get("ph") == "X"
                   and e["name"].startswith("repro/"))
    return tr, spans


def _children(spans):
    """The host steps nested in the one ``repro/scenario`` span, in order
    of their start."""
    top = [s for s in spans if s[2] == "repro/scenario"]
    assert len(top) == 1
    t0, t1, _, args = top[0]
    steps = [s for s in spans if s[2].startswith("repro/host/")]
    assert all(t0 <= s[0] and s[1] <= t1 for s in steps)
    return args, [(s[2][len("repro/host/"):], s[3]) for s in steps]


def _nbytes(*arrays):
    return sum(np.asarray(a).nbytes for a in arrays)


def test_mp_call_spans_nest_in_order_with_their_bytes(tmp_path, problem):
    """MP: prepare, scan, wait, fetch, in that order inside the call; the
    upload is the seven tables, the host inputs, the partition halves and
    the chunk starts; the fetch is the snapshots, the live shares and the
    three counters."""
    topo, _, sol, c = problem
    tr, spans = _traced(tmp_path, _spec(problem, "mp"))
    args, steps = _children(spans)
    assert [s for s, _ in steps] == ["prepare", "scan", "wait", "fetch"]
    assert args == dict(algo="mp", n="60", rounds="8", batch="8", seed="5",
                        sharded="0")
    n_rec = tr.theta_hist.shape[0]
    up = _nbytes(*to_device(topo.tables), sol, c,
                 topo.partition_halves()) + 4 * n_rec
    assert int(steps[0][1]["bytes"]) == up
    down = tr.theta_hist.nbytes + 4 * n_rec + 3 * 4
    assert int(steps[3][1]["bytes"]) == down


def test_cl_call_fetches_stream_totals_while_the_scan_runs(tmp_path,
                                                            problem):
    """CL: the stream's totals are copied back between the scan's dispatch
    and the wait, in a fetch span of their own; the upload is the tables,
    the partition halves and the host data; the second fetch is the
    snapshots.  The scan span states the payload rows the round carries:
    2B partner rows in each of eight (2B, p) f32 arrays."""
    topo, data, sol, _ = problem
    tr, spans = _traced(tmp_path, _spec(problem, "cl"))
    _, steps = _children(spans)
    assert [s for s, _ in steps] == ["prepare", "scan", "fetch", "wait",
                                     "fetch"]
    up = _nbytes(*to_device(topo.tables), topo.partition_halves(),
                 np.asarray(data.x, np.float32),
                 np.asarray(data.mask, np.float32), sol)
    assert int(steps[0][1]["bytes"]) == up
    rounds, batch = RUN_KW["rounds"], RUN_KW["batch"]
    assert int(steps[2][1]["bytes"]) == 3 * rounds * batch + 4 * rounds
    assert int(steps[4][1]["bytes"]) == tr.theta_hist.nbytes
    assert steps[1][1] == dict(payload_rows=str(2 * batch),
                               payload_carry_bytes=str(8 * 2 * batch * 3 * 4))


def test_serve_span_follows_the_engine(tmp_path, problem):
    """A serve stream is driven under ``repro/host/serve`` after the
    engine's steps, with its request count."""
    serve = precompute_serve_stream(60, RUN_KW["rounds"], rate=3.0, seed=1)
    _, spans = _traced(tmp_path, _spec(problem, "mp", serve=serve))
    _, steps = _children(spans)
    names = [s for s, _ in steps]
    assert names[:4] == ["prepare", "scan", "wait", "fetch"]
    assert names[4] == "serve"
    assert steps[4][1] == dict(requests=str(serve.n_requests))


def test_sharded_call_spans(tmp_path, problem):
    """The sharded MP runner (one shard here): partition, stream and
    uploads in prepare, the stream totals fetched before the wait."""
    topo = problem[0]
    tr, spans = _traced(tmp_path, _spec(problem, "mp", sharded=True,
                                        n_shards=1))
    args, steps = _children(spans)
    assert args["sharded"] == "1"
    assert [s for s, _ in steps] == ["prepare", "scan", "fetch", "wait",
                                     "fetch"]
    assert int(steps[0][1]["bytes"]) > _nbytes(*to_device(topo.tables))
    # the snapshots and the one shard's overflow counter, not the final
    # models, which stay on the device
    assert int(steps[4][1]["bytes"]) == tr.theta_hist.nbytes + 4
