"""CL-ADMM (paper §4, Eq. 7) row-sharded over a mesh of chips: the
deployment's inputs (those of ``cl``), its partition, and its calls
through ``run_scenario`` with ``sharded=True``."""

from __future__ import annotations

import dataclasses

import numpy as np

from bench import graph as graph_mod
from bench.algos import cl
from bench.algos.cl import make_inputs  # noqa: F401  (the same inputs)

# the jitted function whose executable runs one call's rounds on each chip
SCAN_MODULE = "_sharded_cl_scan"


def load_assignment(config: dict, topo) -> np.ndarray:
    """Each user's shard, by the configuration's partition: from the cache
    beside the graph (``bench/cache/<config>/partition.npz``), or computed
    once by the system's greedy partitioner and cached."""
    path = graph_mod.CACHE / config["name"] / "partition.npz"
    if path.exists():
        with np.load(path) as z:
            return z["assignment"]
    from repro.simulate import greedy_partition
    how = config["partition"]
    if how["method"] != "greedy":
        raise ValueError(f"unknown partition method {how['method']!r}")
    assignment = greedy_partition(topo, config["shards"], seed=how["seed"])
    graph_mod._save(path, assignment=assignment)
    return assignment


class System(cl.System):
    """One sharded CL-ADMM scenario per call: the ADMM state row-sharded
    over ``shards`` chips, one halo exchange between chips a round."""

    def __init__(self, config: dict, traffic, graph, inputs: dict):
        from repro.launch.sim_mesh import make_sim_mesh
        super().__init__(config, traffic, graph, inputs)
        self.spec = dataclasses.replace(
            self.spec, sharded=True, mesh=make_sim_mesh(config["shards"]),
            assignment=load_assignment(config, self.spec.topology),
            exchange=config["exchange"], halo_codec=config["halo_codec"])

    def call(self, seed: int) -> dict:
        from repro.simulate import run_scenario
        tr = run_scenario(dataclasses.replace(self.spec, seed=seed))
        if tr.overflow:
            raise RuntimeError(f"{tr.overflow} events missed the shards' "
                               f"static buffers: not the scenario's result")
        out = dict(events=tr.events, delivered=tr.delivered,
                   dropped=tr.dropped, invalid=tr.invalid,
                   theta_hist=np.asarray(tr.theta_hist))
        del tr   # the final models stay on the chips until dropped
        return out
