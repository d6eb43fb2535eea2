"""Paper Fig. 2 (right): synchronous vs asynchronous model propagation,
L2 error vs number of pairwise communications (claim C4: async matches the
sync trade-off without any synchronization)."""

from __future__ import annotations

import numpy as np

from repro.core import (closed_form, synchronous, async_gossip, solitary_mean,
                        confidences_from_counts)
from repro.data import mean_estimation_problem


def run(n_agents: int = 100, alpha: float = 0.99, seed: int = 0,
        n_async_runs: int = 5, ticks: int = 4000):
    g, data, targets, _ = mean_estimation_problem(n=n_agents, eps=1.0,
                                                  seed=seed)
    sol = np.asarray(solitary_mean(data))
    conf = np.asarray(confidences_from_counts(data.counts))
    n_edges = len(g.edges())

    rows = []
    # synchronous: one iteration = 2|E| pairwise communications
    for steps in (1, 2, 4, 8, 16):
        th = np.asarray(synchronous(g, sol, conf, alpha, steps=steps))[:, 0]
        rows.append({"algo": "sync", "comms": 2 * n_edges * steps,
                     "l2": float(np.mean((th - targets) ** 2))})
    # asynchronous: one tick = 2 communications; average over runs
    errs = None
    for r in range(n_async_runs):
        tr = async_gossip(g, sol, conf, alpha, steps=ticks, seed=seed + r,
                          record_every=max(ticks // 20, 1))
        e = np.mean((tr.theta_hist[:, :, 0] - targets[None]) ** 2, axis=1)
        errs = e if errs is None else errs + e
        comms = tr.comms_hist
    errs = errs / n_async_runs
    for c, e in zip(comms, errs):
        rows.append({"algo": "async", "comms": int(c), "l2": float(e)})
    # optimum for reference
    star = np.asarray(closed_form(g, sol, conf, alpha))[:, 0]
    rows.append({"algo": "optimal", "comms": -1,
                 "l2": float(np.mean((star - targets) ** 2))})
    return rows


def main(fast: bool = True):
    rows = run(n_agents=60 if fast else 300,
               ticks=2000 if fast else 20000,
               n_async_runs=3 if fast else 100)
    for r in rows:
        print(f"mp_comm,algo={r['algo']},comms={r['comms']},l2={r['l2']:.4f}")
    return rows


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    main(fast=False)
