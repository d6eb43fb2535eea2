"""Model propagation (paper §3, Eq. 6): the deployment's inputs, and its
calls through the system's public entry point ``run_scenario``."""

from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from bench.graph import load_topology

# the jitted function whose executable runs one call's rounds on the device
SCAN_MODULE = "_scenario_scan"


@partial(jax.jit, static_argnames=("n", "p", "m_max"))
def _draw(key, groups, *, n, p, m_max):
    k_m, k_proto, k_noise = jax.random.split(key, 3)
    m = jax.random.randint(k_m, (n,), 1, m_max + 1)
    proto = jax.random.normal(k_proto, (2, p), jnp.float32)
    noise = jax.random.normal(k_noise, (n, p), jnp.float32)
    sol = proto[groups] + noise / jnp.sqrt(m.astype(jnp.float32))[:, None]
    c = m.astype(jnp.float32) / jnp.max(m).astype(jnp.float32)
    return sol, c


def make_inputs(config: dict, graph, seed: int) -> dict:
    """Each user's solitary model from m_i ~ U{1..m_max} local points
    around its spatial half's true model, and its confidence
    c_i = m_i / max m (paper §5.2), drawn on the device in one call."""
    sol, c = _draw(jax.random.PRNGKey(seed), jnp.asarray(graph.groups),
                   n=config["n"], p=config["p"], m_max=config["m_max"])
    return dict(theta_sol=sol, c=c)


class System:
    """One MP scenario per call, B wake-ups a round under the mix's faults."""

    def __init__(self, config: dict, traffic, graph, inputs: dict):
        from repro.simulate import NetworkConditions, ScenarioSpec
        self.spec = ScenarioSpec(
            algo="mp", topology=load_topology(config, graph),
            theta_sol=inputs["theta_sol"], c=inputs["c"],
            alpha=config["alpha"],
            conditions=NetworkConditions(**traffic.conditions),
            rounds=traffic.rounds, batch=traffic.batch,
            record_every=traffic.record_every)

    def call(self, seed: int) -> dict:
        from repro.simulate import run_scenario
        tr = run_scenario(dataclasses.replace(self.spec, seed=seed))
        return dict(events=tr.events, delivered=tr.delivered,
                    dropped=tr.dropped, invalid=tr.invalid,
                    theta_hist=np.asarray(tr.theta_hist))
