"""Paper Fig. 5 (App. E): number of pairwise communications needed to reach
90% of the optimal accuracy vs network size n (kNN graph) — claim C8:
scales ~linearly with n."""

from __future__ import annotations

import numpy as np

from repro.core import (closed_form, solitary_gd, confidences_from_counts,
                        async_gossip)
from repro.data import linear_classification_problem, accuracy


def comms_to_90(n, p=50, seed=0, alpha=0.8, knn=10, max_ticks=20000):
    g, train, test, _ = linear_classification_problem(n=n, p=p, seed=seed,
                                                      knn=knn)
    sol = np.asarray(solitary_gd(train, "hinge", steps=200))
    conf = np.asarray(confidences_from_counts(train.counts))
    star = np.asarray(closed_form(g, sol, conf, alpha))
    target = 0.9 * float(np.mean(accuracy(star, test)))
    tr = async_gossip(g, sol, conf, alpha, steps=max_ticks, seed=seed,
                      record_every=max(max_ticks // 40, 1))
    for c, th in zip(tr.comms_hist, tr.theta_hist):
        if float(np.mean(accuracy(th, test))) >= target:
            return int(c)
    return -1


def run(sizes=(100, 200, 400), seed=0, max_ticks=20000):
    rows = []
    for n in sizes:
        c = comms_to_90(n, seed=seed, max_ticks=max_ticks * max(n // 100, 1))
        rows.append({"n": n, "comms_to_90": c})
    return rows


def main(fast: bool = True):
    rows = run(sizes=(50, 100, 200) if fast else (100, 200, 400, 700, 1000),
               max_ticks=8000 if fast else 30000)
    for r in rows:
        print(f"scalability,n={r['n']},comms_to_90={r['comms_to_90']}")
    # linearity check: comms/n roughly constant
    ratios = [r["comms_to_90"] / r["n"] for r in rows if r["comms_to_90"] > 0]
    if ratios:
        print(f"scalability,ratio_spread={max(ratios)/max(min(ratios),1e-9):.2f}")
    return rows


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    main(fast=False)
