"""CL-ADMM (paper §4, Eq. 7), quadratic loss, exact primal: the
deployment's inputs, and its calls through ``run_scenario``."""

from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from bench.graph import load_topology

# the jitted function whose executable runs one call's rounds on the device
SCAN_MODULE = "_cl_scenario_scan"


@partial(jax.jit, static_argnames=("n", "p", "m_max"))
def _draw(key, groups, *, n, p, m_max):
    k_m, k_proto, k_x = jax.random.split(key, 3)
    m = jax.random.randint(k_m, (n,), 1, m_max + 1)
    mask = (jnp.arange(m_max)[None, :] < m[:, None]).astype(jnp.float32)
    proto = jax.random.normal(k_proto, (2, p), jnp.float32)
    x = (proto[groups][:, None, :]
         + jax.random.normal(k_x, (n, m_max, p), jnp.float32))
    x = x * mask[:, :, None]
    sol = jnp.sum(x, axis=1) / m.astype(jnp.float32)[:, None]
    return x, mask, sol


def make_inputs(config: dict, graph, seed: int) -> dict:
    """Each user's m_i ~ U{1..m_max} samples around its spatial half's
    true model, padded to m_max, and the solitary means that warm-start
    ADMM (paper §4.2), drawn on the device in one call."""
    x, mask, sol = _draw(jax.random.PRNGKey(seed), jnp.asarray(graph.groups),
                         n=config["n"], p=config["p"], m_max=config["m_max"])
    return dict(x=x, mask=mask, theta_sol=sol)


class System:
    """One CL-ADMM scenario per call, B wake-ups a round under the mix."""

    def __init__(self, config: dict, traffic, graph, inputs: dict):
        from repro.core.losses import AgentData
        from repro.simulate import NetworkConditions, ScenarioSpec
        x = inputs["x"]
        data = AgentData(x, jnp.zeros(x.shape[:2], jnp.float32),
                         inputs["mask"])
        self.spec = ScenarioSpec(
            algo="cl", topology=load_topology(config, graph), data=data,
            mu=config["mu"], rho=config["rho"],
            theta_sol=inputs["theta_sol"],
            conditions=NetworkConditions(**traffic.conditions),
            rounds=traffic.rounds, batch=traffic.batch,
            record_every=traffic.record_every)

    def call(self, seed: int) -> dict:
        from repro.simulate import run_scenario
        tr = run_scenario(dataclasses.replace(self.spec, seed=seed))
        out = dict(events=tr.events, delivered=tr.delivered,
                   dropped=tr.dropped, invalid=tr.invalid,
                   theta_hist=np.asarray(tr.theta_hist))
        del tr   # the final ADMM state stays on the device until dropped
        return out
