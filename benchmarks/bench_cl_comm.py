"""Paper Fig. 3 (right): async CL vs sync CL vs async MP — test accuracy vs
pairwise communications (claim C7: async CL matches sync CL; MP converges
~an order of magnitude faster and is a good warm start)."""

from __future__ import annotations

import numpy as np

from repro.core import (solitary_gd, confidences_from_counts, async_gossip,
                        async_admm, sync_admm)
from repro.data import linear_classification_problem, accuracy


def run(n=60, p=50, seed=0, alpha=0.8, mu=0.05, rho=1.0,
        sync_steps=40, async_ticks=4000):
    g, train, test, targets = linear_classification_problem(n=n, p=p,
                                                            seed=seed)
    sol = np.asarray(solitary_gd(train, "hinge", steps=250))
    conf = np.asarray(confidences_from_counts(train.counts))
    n_edges = len(g.edges())
    rows = []

    tr = sync_admm(g, train, mu, rho, "hinge", steps=sync_steps, k_steps=12,
                   lr=0.05, theta_sol=sol)
    for i in range(0, sync_steps, max(sync_steps // 10, 1)):
        rows.append({"algo": "cl_sync", "comms": 2 * n_edges * (i + 1),
                     "acc": float(np.mean(accuracy(tr.theta_hist[i], test)))})

    tra = async_admm(g, train, mu, rho, "hinge", steps=async_ticks,
                     k_steps=12, lr=0.05,
                     record_every=max(async_ticks // 10, 1), theta_sol=sol)
    for c, th in zip(tra.comms_hist, tra.theta_hist):
        rows.append({"algo": "cl_async", "comms": int(c),
                     "acc": float(np.mean(accuracy(th, test)))})

    trm = async_gossip(g, sol, conf, alpha, steps=async_ticks, seed=seed,
                       record_every=max(async_ticks // 10, 1))
    for c, th in zip(trm.comms_hist, trm.theta_hist):
        rows.append({"algo": "mp_async", "comms": int(c),
                     "acc": float(np.mean(accuracy(th, test)))})
    rows.append({"algo": "solitary", "comms": 0,
                 "acc": float(np.mean(accuracy(sol, test)))})
    return rows


def main(fast: bool = True):
    rows = run(n=40 if fast else 100, sync_steps=20 if fast else 60,
               async_ticks=1500 if fast else 10000)
    for r in rows:
        print(f"cl_comm,algo={r['algo']},comms={r['comms']},acc={r['acc']:.3f}")
    return rows


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    main(fast=False)
