"""Unified scenario API: one frozen spec, one entry point (DESIGN.md §16).

``run_scenario(ScenarioSpec(...))`` replaces the six historical entry
points (``run_{mp,cl,joint}_scenario`` and their ``_sharded`` twins),
which survive as thin deprecated wrappers that build a spec and dispatch
— bit-for-bit equivalent by construction (tests/test_scenario_api.py
asserts it for every algo x sharding cell).

The spec also carries the one capability the legacy signatures never
had: an optional *inference-request stream* (``serve``).  When set, the
driver runs the personalization service against the scan's committed
record-chunk snapshots — the read/write split of ``repro.serve.store``:
the jitted gossip scan is the sole writer, requests read immutable
committed state, so serving cannot perturb the trajectory and
``trace.theta_hist`` is bit-for-bit identical to the serve-free run.
"""

from __future__ import annotations

import dataclasses
import warnings
from typing import Any, Optional

import jax.numpy as jnp
import numpy as np

from repro.core.sparse import record_chunks
from repro.telemetry.metrics import (stream_dirty_chunks,
                                     stream_staleness_chunks)

from . import engines as _engines
from . import partition as _partition
from . import spans
from .scheduler import (EventStream, NetworkConditions, ServeStream,
                        precompute_event_stream, serve_chunk_requests)

_ALGOS = ("mp", "cl", "joint")


@dataclasses.dataclass(frozen=True, eq=False)
class ScenarioSpec:
    """Everything that defines one collaborative-learning scenario run.

    Frozen (build once, ``dataclasses.replace`` for sweeps; ``eq=False``
    because ndarray payloads aren't hashable).  Field groups:

    core:     algo ("mp" | "cl" | "joint"), topology, conditions, rounds,
              batch, seed, record_every
    mp/joint: theta_sol (pure targets), c (confidence), alpha (Eq. 3 mix)
    cl:       data (AgentData), mu, rho (Eq. 7 / ADMM), state (warm ADMM
              state; single-device only), theta_sol (warm start), primal
              (PrimalSolver strategy — ``core.primal``; None = the exact
              closed-form quadratic solve)
    joint:    eta_graph, lam, graph_every, prune_eps (DESIGN.md §13)
    events:   stream — precomputed EventStream override (cl/joint; the mp
              engine draws inline by the identical RNG schedule and
              rejects an override)
    exec:     backend (fused round_step), telemetry (TelemetryConfig)
    sharding: sharded plus the partitioned-runner knobs (n_shards, mesh,
              assignment, local_batch, exchange, halo_codec,
              partition_seed, recompact_every/frac — joint only)
    serving:  serve (ServeStream of inference requests interleaved with
              the gossip rounds), serve_batch (decode batch width)
    """

    algo: str
    topology: Any
    conditions: NetworkConditions
    rounds: int
    batch: int
    seed: int = 0
    record_every: int = 10
    # mp / joint payload
    theta_sol: Any = None
    c: Any = None
    alpha: float = 0.5
    # cl payload
    data: Any = None
    mu: Optional[float] = None
    rho: Optional[float] = None
    state: Any = None
    primal: Any = None
    # joint graph-learning knobs
    eta_graph: float = 0.0
    lam: float = 1.0
    graph_every: int = 1
    prune_eps: Optional[float] = None
    # event stream / execution
    stream: Optional[EventStream] = None
    backend: Any = None
    telemetry: Any = None
    # sharding
    sharded: bool = False
    n_shards: Optional[int] = None
    mesh: Any = None
    assignment: Any = None
    local_batch: Optional[int] = None
    exchange: str = "all_gather"
    halo_codec: Any = "f32"
    partition_seed: int = 0
    recompact_every: Optional[int] = None
    recompact_frac: float = 0.25
    # serving
    serve: Optional[ServeStream] = None
    serve_batch: int = 256

    def __post_init__(self):
        if self.algo not in _ALGOS:
            raise ValueError(f"unknown algo {self.algo!r}; one of {_ALGOS}")
        if self.algo == "mp" and self.stream is not None:
            raise ValueError(
                "algo='mp' draws its event stream inline (identical RNG "
                "schedule); a stream override is only supported for "
                "'cl'/'joint'")
        if self.primal is not None and self.algo != "cl":
            raise ValueError(
                "primal solvers plug into the CL-ADMM engines only "
                "(algo='cl')")

    def _require(self, **fields):
        for name, val in fields.items():
            if val is None:
                raise ValueError(
                    f"algo={self.algo!r} requires ScenarioSpec.{name}")


def run_scenario(spec: ScenarioSpec):
    """Run the scenario a :class:`ScenarioSpec` describes.

    Dispatches to the algo's engine (single-device or partitioned), then
    — if the spec carries a ``serve`` stream — drives the personalization
    service over the committed snapshots and attaches the resulting
    ``ServeReport`` as ``trace.serve`` (plus the cumulative serve
    counters on ``trace.telemetry`` when telemetry is enabled).  Returns
    the engine's trace type unchanged otherwise.

    The call runs inside the profiler span ``repro/scenario``, whose args
    identify it; the engines' host steps nest inside it
    (``repro.simulate.spans``).
    """
    with spans.scenario(
        algo=spec.algo,
        n=spec.topology.n,
        rounds=spec.rounds,
        batch=spec.batch,
        seed=spec.seed,
        sharded=spec.sharded,
    ):
        trace = _run_engine(spec)
        if spec.serve is not None:
            with spans.host("serve", requests=spec.serve.n_requests):
                trace = _drive_serve(spec, trace)
    return trace


def _run_engine(spec: ScenarioSpec):
    """The algo's engine (single-device or partitioned) on the spec."""
    common = dict(conditions=spec.conditions, rounds=spec.rounds,
                  batch=spec.batch, seed=spec.seed,
                  record_every=spec.record_every, telemetry=spec.telemetry)
    shard_kw = dict(n_shards=spec.n_shards, mesh=spec.mesh,
                    assignment=spec.assignment, local_batch=spec.local_batch,
                    exchange=spec.exchange, halo_codec=spec.halo_codec,
                    partition_seed=spec.partition_seed)
    if spec.sharded and spec.backend is not None and spec.algo != "joint":
        raise ValueError(
            "backend overrides apply to the single-device engines and the "
            "sharded joint runner only")
    if spec.algo == "mp":
        spec._require(theta_sol=spec.theta_sol, c=spec.c)
        if spec.sharded:
            trace = _partition.run_mp_scenario_sharded(
                spec.topology, spec.theta_sol, spec.c, spec.alpha,
                **common, **shard_kw)
        else:
            trace = _engines.run_mp_scenario(
                spec.topology, spec.theta_sol, spec.c, spec.alpha,
                backend=spec.backend, **common)
    elif spec.algo == "cl":
        spec._require(data=spec.data, mu=spec.mu, rho=spec.rho,
                      theta_sol=spec.theta_sol)
        if spec.sharded:
            if spec.state is not None:
                raise ValueError(
                    "warm ADMM state is single-device only (the sharded "
                    "runner rebuilds its own sharded state)")
            trace = _partition.run_cl_scenario_sharded(
                spec.topology, spec.data, spec.mu, spec.rho,
                theta_sol=spec.theta_sol, stream=spec.stream,
                primal=spec.primal, **common, **shard_kw)
        else:
            trace = _engines.run_cl_scenario(
                spec.topology, spec.data, spec.mu, spec.rho,
                theta_sol=spec.theta_sol, state=spec.state,
                stream=spec.stream, backend=spec.backend,
                primal=spec.primal, **common)
    else:  # joint
        spec._require(theta_sol=spec.theta_sol, c=spec.c)
        joint_kw = dict(eta_graph=spec.eta_graph, lam=spec.lam,
                        graph_every=spec.graph_every,
                        prune_eps=spec.prune_eps, stream=spec.stream,
                        backend=spec.backend)
        if spec.sharded:
            trace = _partition.run_joint_scenario_sharded(
                spec.topology, spec.theta_sol, spec.c, spec.alpha,
                recompact_every=spec.recompact_every,
                recompact_frac=spec.recompact_frac,
                **common, **shard_kw, **joint_kw)
        else:
            trace = _engines.run_joint_scenario(
                spec.topology, spec.theta_sol, spec.c, spec.alpha,
                **common, **joint_kw)
    return trace


def _drive_serve(spec: ScenarioSpec, trace):
    """Serve the spec's inference-request stream from the finished trace.

    The read/write split in action (DESIGN.md §16): per record chunk the
    driver *commits* the chunk's snapshot (theta rows + the host-replayed
    staleness counters) to an agent-state store, *invalidates* the mixed
    model cache at exactly the agents the chunk's deliveries rewrote, and
    *serves* every request whose arrival round falls inside the chunk
    from the committed state (post-update visibility).  Reads never touch
    the scan, so ``trace.theta_hist`` is untouched by construction.
    """
    from repro.serve import (AgentStateStore, CollabServeEngine,
                             ShardedAgentStateStore)

    topo = spec.topology
    n = topo.n
    record_every, n_rec = record_chunks(spec.rounds, spec.record_every)
    total_rounds = n_rec * record_every
    stream = spec.stream
    if stream is None:
        # the engines' own schedule (scheduler.precompute_event_stream is
        # documented to reproduce the inline draws exactly)
        stream = precompute_event_stream(
            topo.device_tables(), jnp.asarray(topo.partition_halves()),
            spec.conditions, spec.batch, spec.seed, total_rounds)
    dirty = stream_dirty_chunks(stream, n, n_rec, record_every)
    staleness = stream_staleness_chunks(stream, n, n_rec, record_every)
    requests = serve_chunk_requests(spec.serve, n_rec, record_every)

    p = int(trace.theta_hist.shape[-1])
    if spec.sharded:
        _, P_, _, part = _partition._sharded_setup(
            topo, spec.n_shards, spec.mesh, spec.assignment,
            spec.partition_seed)
        store = ShardedAgentStateStore(part.owner, part.local_pos, p, P_)
    else:
        store = AgentStateStore(n, p)
    eng = CollabServeEngine(store, n, p, batch_size=spec.serve_batch)

    counters = np.zeros((4, n_rec), np.int64)
    for ci in range(n_rec):
        eng.commit((ci + 1) * record_every, trace.theta_hist[ci],
                   staleness[ci], dirty[ci])
        users, _rounds = requests[ci]
        if users.size:
            eng.serve(users)
        counters[:, ci] = (eng.requests, eng.cache.hits, eng.cache.misses,
                           eng.cache.invalidations)
    report = eng.report(*counters)
    trace = dataclasses.replace(trace, serve=report)
    if trace.telemetry is not None:
        trace.telemetry.serve_requests = counters[0]
        trace.telemetry.serve_hits = counters[1]
        trace.telemetry.serve_misses = counters[2]
        trace.telemetry.serve_invalidations = counters[3]
    return trace


# ---------------------------------------------------------------------------
# deprecated legacy entry points (thin wrappers over run_scenario)
# ---------------------------------------------------------------------------


def _warn_legacy(old: str):
    warnings.warn(
        f"{old} is deprecated; build a ScenarioSpec and call "
        f"run_scenario(spec) instead (migration table: DESIGN.md §16)",
        DeprecationWarning, stacklevel=3)


def run_mp_scenario(topo, theta_sol, c, alpha, conditions, rounds, batch,
                    seed=0, record_every=10, telemetry=None, backend=None):
    """Deprecated wrapper: ``run_scenario(ScenarioSpec(algo="mp", ...))``."""
    _warn_legacy("run_mp_scenario")
    return run_scenario(ScenarioSpec(
        algo="mp", topology=topo, theta_sol=theta_sol, c=c, alpha=alpha,
        conditions=conditions, rounds=rounds, batch=batch, seed=seed,
        record_every=record_every, telemetry=telemetry, backend=backend))


def run_cl_scenario(topo, data, mu, rho, conditions, rounds, batch,
                    seed=0, record_every=10, theta_sol=None, state=None,
                    stream=None, backend=None, telemetry=None):
    """Deprecated wrapper: ``run_scenario(ScenarioSpec(algo="cl", ...))``."""
    _warn_legacy("run_cl_scenario")
    return run_scenario(ScenarioSpec(
        algo="cl", topology=topo, data=data, mu=mu, rho=rho,
        conditions=conditions, rounds=rounds, batch=batch, seed=seed,
        record_every=record_every, theta_sol=theta_sol, state=state,
        stream=stream, backend=backend, telemetry=telemetry))


def run_joint_scenario(topo, theta_sol, c, alpha, conditions, rounds, batch,
                       seed=0, record_every=10, *, eta_graph=0.0, lam=1.0,
                       graph_every=1, prune_eps=None, stream=None,
                       backend=None, telemetry=None):
    """Deprecated wrapper: ``run_scenario(ScenarioSpec(algo="joint", ...))``."""
    _warn_legacy("run_joint_scenario")
    return run_scenario(ScenarioSpec(
        algo="joint", topology=topo, theta_sol=theta_sol, c=c, alpha=alpha,
        conditions=conditions, rounds=rounds, batch=batch, seed=seed,
        record_every=record_every, eta_graph=eta_graph, lam=lam,
        graph_every=graph_every, prune_eps=prune_eps, stream=stream,
        backend=backend, telemetry=telemetry))


def run_mp_scenario_sharded(topo, theta_sol, c, alpha, conditions, rounds,
                            batch, seed=0, record_every=10, *,
                            n_shards=None, mesh=None, assignment=None,
                            local_batch=None, exchange="all_gather",
                            halo_codec="f32", partition_seed=0,
                            telemetry=None):
    """Deprecated wrapper: ``ScenarioSpec(algo="mp", sharded=True)``."""
    _warn_legacy("run_mp_scenario_sharded")
    return run_scenario(ScenarioSpec(
        algo="mp", topology=topo, theta_sol=theta_sol, c=c, alpha=alpha,
        conditions=conditions, rounds=rounds, batch=batch, seed=seed,
        record_every=record_every, telemetry=telemetry, sharded=True,
        n_shards=n_shards, mesh=mesh, assignment=assignment,
        local_batch=local_batch, exchange=exchange, halo_codec=halo_codec,
        partition_seed=partition_seed))


def run_cl_scenario_sharded(topo, data, mu, rho, conditions, rounds, batch,
                            seed=0, record_every=10, *, theta_sol=None,
                            n_shards=None, mesh=None, assignment=None,
                            local_batch=None, exchange="all_gather",
                            halo_codec="f32", partition_seed=0,
                            stream=None, telemetry=None):
    """Deprecated wrapper: ``ScenarioSpec(algo="cl", sharded=True)``."""
    _warn_legacy("run_cl_scenario_sharded")
    return run_scenario(ScenarioSpec(
        algo="cl", topology=topo, data=data, mu=mu, rho=rho,
        conditions=conditions, rounds=rounds, batch=batch, seed=seed,
        record_every=record_every, theta_sol=theta_sol, stream=stream,
        telemetry=telemetry, sharded=True, n_shards=n_shards, mesh=mesh,
        assignment=assignment, local_batch=local_batch, exchange=exchange,
        halo_codec=halo_codec, partition_seed=partition_seed))


def run_joint_scenario_sharded(topo, theta_sol, c, alpha, conditions,
                               rounds, batch, seed=0, record_every=10, *,
                               eta_graph=0.0, lam=1.0, graph_every=1,
                               prune_eps=None, recompact_every=None,
                               recompact_frac=0.25, n_shards=None,
                               mesh=None, assignment=None, local_batch=None,
                               exchange="all_gather", halo_codec="f32",
                               partition_seed=0, stream=None, backend=None,
                               telemetry=None):
    """Deprecated wrapper: ``ScenarioSpec(algo="joint", sharded=True)``."""
    _warn_legacy("run_joint_scenario_sharded")
    return run_scenario(ScenarioSpec(
        algo="joint", topology=topo, theta_sol=theta_sol, c=c, alpha=alpha,
        conditions=conditions, rounds=rounds, batch=batch, seed=seed,
        record_every=record_every, eta_graph=eta_graph, lam=lam,
        graph_every=graph_every, prune_eps=prune_eps,
        recompact_every=recompact_every, recompact_frac=recompact_frac,
        stream=stream, backend=backend, telemetry=telemetry, sharded=True,
        n_shards=n_shards, mesh=mesh, assignment=assignment,
        local_batch=local_batch, exchange=exchange, halo_codec=halo_codec,
        partition_seed=partition_seed))
